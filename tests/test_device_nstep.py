"""What PQL's deployment added to the device pool (actors/device_pool.py,
ops/exploration.py, envs/jax_envs.py, train.py), at small sizes on the CPU:
the n-step window inside the rollout scan against the host accumulator, the
stand-in environment, the Gaussian ladder, the ratio gate's pace, the carry
with its window through a save and a restore, and what the `refresh` phase
does with and without a host worker."""

import hashlib
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ddpg_tpu.actors.device_pool import DeviceActorPool, program_specs
from distributed_ddpg_tpu.actors.worker import _flush_truncated
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.envs import jax_envs
from distributed_ddpg_tpu.envs.jax_envs import STAND_IN_ID, IsaacHumanoidStandIn
from distributed_ddpg_tpu.learner import init_train_state
from distributed_ddpg_tpu.ops.exploration import sigma_ladder, vector_env_step
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.replay.device import DeviceReplay
from distributed_ddpg_tpu.replay.nstep import NStepAccumulator

E, K, N = 8, 4, 3
OBS, ACT = IsaacHumanoidStandIn.obs_dim, IsaacHumanoidStandIn.act_dim


def cfg(**kw):
    base = dict(
        backend="jax_tpu", env_id=STAND_IN_ID, actor_backend="device", num_actors=0, device_actor_envs=E,
        device_actor_chunk=K, actor_hidden=(32, 16, 8), critic_hidden=(32, 16, 8), batch_size=64,
        replay_capacity=4096, twin_critic=True, policy_delay=2, action_insert_layer=0, tau=0.05,
        exploration="gaussian", seed=5,
    )
    base.update(kw)
    return DDPGConfig(**base)


def one_device_mesh():
    return mesh_lib.make_mesh(data_axis=1, model_axis=1, devices=jax.devices()[:1])


def pool_and_ring(config, mesh):
    pool = DeviceActorPool(config, mesh=mesh)
    pool.set_params(init_train_state(config, OBS, ACT, config.seed).actor_params)
    ring = DeviceReplay(config.replay_capacity, OBS, ACT, mesh=mesh, block_size=64, async_ship=False)
    return pool, ring


def landed(ring, rows):
    return np.asarray(jax.device_get(ring.storage))[:rows]


class Terminating(IsaacHumanoidStandIn):
    BOX = 0.13  # x_0 ~ U(-0.1, 0.1) and noise of 0.02 a step: most episodes leave it within ten


class Truncating(IsaacHumanoidStandIn):
    max_episode_steps = 4
    BOX = 100.0


class Brief(IsaacHumanoidStandIn):
    max_episode_steps = 2  # every episode ends inside the first three steps,
    BOX = 0.19  # some by termination


@pytest.mark.parametrize("env_cls,ends", [(Terminating, "terminated"), (Truncating, "truncated"), (Brief, "both")])
def test_device_fold_emits_the_host_accumulators_rows(monkeypatch, env_cls, ends):
    """Two pools on one seed, so one trajectory: the 1-step pool's rows,
    pushed through replay/nstep.py as a worker pushes them (the truncation
    flush included), against what the 3-step pool landed. One row a step and
    environment, in the order the episodes' steps were taken; the host flushes
    an episode's tail at its end, the device emits it over the next two
    steps."""
    monkeypatch.setitem(jax_envs._JAX_ENVS, STAND_IN_ID, env_cls)
    mesh, chunks = one_device_mesh(), 3
    one, ring1 = pool_and_ring(cfg(n_step=1), mesh)
    three, ring3 = pool_and_ring(cfg(n_step=N), mesh)
    assert three.pending_rows == (N - 1) * E and three.steps_done == (N - 1) * E  # primed
    for _ in range(chunks + 1):
        one.run_chunk(ring1)
    for _ in range(chunks):
        three.run_chunk(ring3)
    steps = N - 1 + chunks * K
    flat = landed(ring1, steps * E).reshape(steps, E, -1)
    got = landed(ring3, chunks * K * E).reshape(chunks * K, E, -1)
    gamma, n_term, n_trunc, short = three.config.gamma, 0, 0, 0
    for e in range(E):
        acc, want, lengths = NStepAccumulator(N, gamma), [], []
        emit = acc._emit

        def noting_length(pend, boot, terminal, length, emit=emit, lengths=lengths):
            lengths.append(length)
            return emit(pend, boot, terminal=terminal, length=length)

        acc._emit = noting_length
        for t in range(steps):
            row = flat[t, e]
            obs, action, reward = row[:OBS], row[OBS:OBS + ACT], row[OBS + ACT]
            boot = row[OBS + ACT + 2:2 * OBS + ACT + 2]
            terminated = row[OBS + ACT + 1] == 0.0
            # a reset shows in the next step's observation (the stand-in's is its state)
            truncated = not terminated and t + 1 < steps and not np.array_equal(flat[t + 1, e, :OBS], boot)
            want += list(acc.push(obs[None], action[None], [reward], [terminated], boot[None]))
            if truncated:
                want += _flush_truncated(acc, boot)
            if terminated or truncated:
                acc.reset()
            n_term, n_trunc = n_term + terminated, n_trunc + truncated
        emitted = got[:, e]
        assert len(want) >= len(emitted)
        for t, (o, a, r, d, nobs) in enumerate(want[: len(emitted)]):
            np.testing.assert_array_equal(emitted[t, :OBS], o)
            np.testing.assert_array_equal(emitted[t, OBS:OBS + ACT], a)
            np.testing.assert_allclose(emitted[t, OBS + ACT], r, rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(emitted[t, OBS + ACT + 1], d, rtol=1e-6)
            np.testing.assert_array_equal(emitted[t, OBS + ACT + 2:2 * OBS + ACT + 2], nobs)
            assert emitted[t, -1] == 1.0
        short += sum(length < N for length in lengths[: len(emitted)])
    if ends in ("terminated", "both"):
        assert n_term > 0
    if ends in ("truncated", "both"):
        assert n_trunc > 0
    # the pool's own count of rows folded over fewer than n steps, and its books
    assert int(jax.device_get(three._carry.short_rows)) == short > 0
    assert three.steps_done == steps * E and three.pending_rows == (N - 1) * E


def test_stand_in_is_a_function_of_its_key_at_the_sources_shapes():
    env = IsaacHumanoidStandIn()
    assert (env.obs_dim, env.act_dim, env.max_episode_steps) == (108, 21, 1000)
    assert env.action_low.tolist() == [-1.0] * 21 and env.action_high.tolist() == [1.0] * 21
    key = jax.random.PRNGKey(7)
    s = env.init(key)
    assert s.x.shape == (108,) and env.observe(s).shape == (108,)
    u = jnp.full((21,), 0.3)
    a, b = env.step(s, u, jax.random.PRNGKey(9)), env.step(s, u, jax.random.PRNGKey(9))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    other = env.step(s, u, jax.random.PRNGKey(10))
    assert not np.array_equal(a.boot_obs, other.boot_obs)  # the process noise is the key's
    assert a.reward.shape == () and a.boot_obs.shape == (108,)
    # the matrices are the environment's own: two instances, one system
    np.testing.assert_array_equal(env.a, IsaacHumanoidStandIn().a)
    assert np.linalg.norm(env.a, 2) == pytest.approx(env.RHO, rel=1e-5)
    # actions are clipped to the box
    np.testing.assert_array_equal(
        env.step(s, jnp.full((21,), 5.0), key).boot_obs, env.step(s, jnp.ones((21,)), key).boot_obs)


@pytest.mark.parametrize("end", ["terminated", "truncated"])
def test_stand_in_ends_an_episode_both_ways(end):
    env = IsaacHumanoidStandIn()
    key = jax.random.PRNGKey(3)
    if end == "terminated":
        s = jax_envs.StandInState(x=jnp.full((108,), 0.9).at[0].set(1.5), t=jnp.asarray(10, jnp.int32))
    else:
        s = jax_envs.StandInState(x=jnp.zeros((108,)), t=jnp.asarray(999, jnp.int32))
    out = env.step(s, jnp.zeros((21,)), key)
    assert bool(out.done)
    assert bool(out.terminated) == (end == "terminated")
    # auto-reset: the policy's next observation is a fresh episode's, the
    # row's next observation the step's own
    assert int(out.state.t) == 0 and float(jnp.max(jnp.abs(out.obs))) <= env.INIT
    assert not np.array_equal(out.obs, out.boot_obs)
    if end == "terminated":
        assert float(jnp.max(jnp.abs(out.boot_obs))) > env.BOX
    mid = env.step(jax_envs.StandInState(x=jnp.zeros((108,)), t=jnp.asarray(5, jnp.int32)), jnp.zeros((21,)), key)
    assert not bool(mid.done) and np.array_equal(mid.obs, mid.boot_obs)


def test_sigma_ladder_ends_spacing_and_what_each_environment_adds():
    config = cfg(device_actor_envs=16)
    ladder = np.asarray(sigma_ladder(config, 16))
    assert ladder[0] == pytest.approx(0.05) and ladder[-1] == pytest.approx(0.8)
    np.testing.assert_allclose(np.diff(ladder), (0.8 - 0.05) / 15, rtol=1e-5)
    env = IsaacHumanoidStandIn()
    key = jax.random.PRNGKey(1)
    state = jax.vmap(env.init)(jax.random.split(key, 16))
    obs = jax.vmap(env.observe)(state)
    params = init_train_state(config, OBS, ACT, 0).actor_params
    one, low, high = jnp.ones((ACT,)), -jnp.ones((ACT,)), jnp.ones((ACT,))
    ou = jnp.zeros((16, ACT))
    _, new_ou, action, _, _ = vector_env_step(config, env, 16, params, state, obs, ou, key, one, 0.0, low, high)
    _, _, quiet, _, _ = vector_env_step(
        config.replace(explore_sigma_min=0.0, explore_sigma_max=0.0), env, 16, params, state, obs, ou, key,
        one, 0.0, low, high)
    xi = jax.random.normal(jax.random.split(key, 4)[1], (16, ACT))
    np.testing.assert_allclose(action, jnp.clip(quiet + ladder[:, None] * xi, -1.0, 1.0), atol=1e-6)
    np.testing.assert_array_equal(new_ou, ou)  # no state between steps
    pool = DeviceActorPool(config, mesh=one_device_mesh())
    assert pool.sigma_ends == (pytest.approx(0.05), pytest.approx(0.8))
    with pytest.raises(ValueError, match="gaussian"):
        DDPGConfig(exploration="gaussian")  # the host pool has OU alone
    with pytest.raises(ValueError, match="exploration"):
        DDPGConfig(exploration="uniform")


def test_ou_rollout_lowers_to_the_parents_text():
    """The default process and a 1-step pool: the carry has no window leaf and
    the lowered text of the registry's rollout program is, to the bit, the
    one commit b7758e4 lowered (jax 0.9.0; the hash was taken there, with the
    program's new name written back to the old). The ladder's fields do not
    enter it."""
    spec = next(s for s in program_specs() if s.name == "devactor.rollout")

    def text(build):
        return build.fn.lower(*build.args).as_text().replace("jit_devactor_rollout", "jit_rollout")

    built = spec.build()
    assert built.args[1].window is None and built.args[1].short_rows is None
    assert hashlib.sha256(text(built).encode()).hexdigest()[:16] == "9a8b460b7d7f4d55"


def test_carry_with_its_window_restores_and_continues_bit_for_bit():
    mesh = one_device_mesh()
    config = cfg(n_step=N)
    a, ring_a = pool_and_ring(config, mesh)
    a.run_chunk(ring_a)
    a.run_chunk(ring_a)
    saved = a.carry_state_dict()
    fresh = DeviceActorPool(config, mesh=mesh)
    assert len(saved) == len(jax.tree.leaves(fresh._carry)) == len(jax.tree.leaves(DeviceActorPool(
        cfg(n_step=1), mesh=mesh)._carry)) + 3  # the window's rows and flags, and the short-row count
    a.run_chunk(ring_a)
    b = DeviceActorPool(config, mesh=mesh)
    assert b.load_carry_state(saved)
    b.set_params(init_train_state(config, OBS, ACT, config.seed).actor_params)
    assert b.steps_done == 0 and b.pending_rows == (N - 1) * E  # restored: no priming steps taken again
    ring_b = DeviceReplay(config.replay_capacity, OBS, ACT, mesh=mesh, block_size=64, async_ship=False)
    b.run_chunk(ring_b)
    np.testing.assert_array_equal(landed(ring_a, 3 * K * E)[2 * K * E:], landed(ring_b, K * E))
    for x, y in zip(jax.tree.leaves(a._carry), jax.tree.leaves(b._carry)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # a 1-step run's sidecar does not fit a 3-step pool: fresh episodes, primed at the swap
    c = DeviceActorPool(config, mesh=mesh)
    assert not c.load_carry_state(DeviceActorPool(cfg(n_step=1), mesh=mesh).carry_state_dict())
    assert c.pending_rows == 0


def test_parent_pr33_checkpoint_still_passes_a_run_with_the_new_fields(tmp_path):
    """tests/ckpt_fixtures/parent_pr33 was written before `exploration` and
    the ladder's ends existed: a run like its writer still takes it, whatever
    the new fields say (they shape no learner state)."""
    from distributed_ddpg_tpu import checkpoint as ckpt_lib

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ckpt_fixtures", "parent_pr33")
    directory = str(tmp_path / "ckpt")
    shutil.copytree(src, directory)
    saved = json.load(open(os.path.join(directory, "config_3.json")))
    assert not {"exploration", "explore_sigma_min", "explore_sigma_max"} & set(saved)
    writer = DDPGConfig(actor_hidden=(16, 16), critic_hidden=(16, 16), batch_size=8, seed=3)
    ckpt_lib.check_config_compatible(directory, 3, writer)
    ckpt_lib.check_config_compatible(directory, 3, writer.replace(explore_sigma_max=0.5))
    state, step, env_steps = ckpt_lib.restore(directory, init_train_state(writer, 5, 3, seed=1), config=writer)
    assert (step, env_steps, int(state.step)) == (3, 48, 3)


def test_the_gate_holds_the_sources_rows_per_update_over_twenty_launches(tmp_path, monkeypatch, one_chip):
    """PQL's a:v ratio at a small size: 8 rows an update (`max_ingest_ratio`),
    2 updates a launch and 16 rows a rollout chunk, so the gate admits one
    rollout a launch and the rows written stay within one chunk of
    min_fill + 8 * updates at every dispatch; the summary's books close with
    the windows' pending rows where benchmarks/run.py looks for them."""
    from distributed_ddpg_tpu.train import train_jax

    seen = []
    run_chunk = DeviceActorPool.run_chunk

    def noting(self, replay, newest_version=0):
        rows = run_chunk(self, replay, newest_version)
        seen.append((newest_version, self._rows_emitted))
        return rows

    monkeypatch.setattr(DeviceActorPool, "run_chunk", noting)
    config = cfg(
        n_step=N, device_actor_chunk=2, learner_chunk=2, max_ingest_ratio=8.0, replay_min_size=64,
        warmup_uniform_steps=64, total_env_steps=64 + 16 + 16 * 30, eval_every=0,
        log_path=str(tmp_path / "m.jsonl"),
    )
    out = train_jax(config)
    steady = [(v, rows) for v, rows in seen if v > 0]
    assert len(steady) >= 20
    for version, rows in steady:
        assert abs(rows - (64 + 8 * version)) <= 16, (version, rows)
    assert {b[1] - a[1] for a, b in zip(steady, steady[1:])} == {16}  # one rollout a launch
    assert {b[0] - a[0] for a, b in zip(steady, steady[1:])} == {2}
    # every env step is in the ring or still in a window: nothing else
    assert out["ingest_queue_rows"] == (N - 1) * E
    assert out["buffer_fill"] + out["ingest_queue_rows"] == out["env_steps"] == out["devactor_env_steps"]
    final = [json.loads(line) for line in open(config.log_path)][-1]
    assert final["kind"] == "final" and final["ring_wraps"] == 0 and "devactor_nstep_short_pct" in final
    header = json.loads(open(config.log_path).readline())
    assert (header["devactor_sigma_min"], header["devactor_sigma_max"]) == (0.05, 0.8)


@pytest.mark.parametrize("num_actors", [0, 1])
def test_refresh_phase_issues_a_d2h_only_where_a_host_worker_reads_it(tmp_path, monkeypatch, one_chip, num_actors):
    """With no host worker the `refresh` phase brackets the pool's pointer
    swap, once a launch, and the loop fetches the actor's parameters only
    where a run always does (its start, its end); with one worker beside the
    pool every refresh is the d2h and the broadcast it was."""
    from distributed_ddpg_tpu.actors.pool import ActorPool
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.train import train_jax

    calls = {"d2h": 0, "broadcast": 0}
    to_host, broadcast = ShardedLearner.actor_params_to_host, ActorPool.broadcast

    def counting_d2h(self):
        calls["d2h"] += 1
        return to_host(self)

    def counting_broadcast(self, *a, **kw):
        calls["broadcast"] += 1
        return broadcast(self, *a, **kw)

    monkeypatch.setattr(ShardedLearner, "actor_params_to_host", counting_d2h)
    monkeypatch.setattr(ActorPool, "broadcast", counting_broadcast)
    config = cfg(
        env_id="Pendulum-v1", exploration="ou", twin_critic=False, policy_delay=1, action_insert_layer=1,
        n_step=N, num_actors=num_actors, device_actor_chunk=2, learner_chunk=2, replay_min_size=64,
        # a host worker beside the pool delivers a few thousand rows a second: a budget for 20 launches and more
        total_env_steps=64 + 16 * 60 if num_actors == 0 else 30_000, eval_every=0, param_refresh_every=2,
        param_refresh_interval_s=0.0, fused_beat="off", log_path=str(tmp_path / "m.jsonl"),
    )
    out = train_jax(config)
    records = [json.loads(line) for line in open(config.log_path)]
    launches = out["learner_steps"] // 2
    assert launches >= 20
    if num_actors == 0:
        # no broadcast at all (a pool without workers shares nothing); the start's, the final evaluation's and the checksum's d2h
        assert calls["broadcast"] == 0 and calls["d2h"] <= 3
        # every rollout read the newest parameters
        assert [r["staleness_mean"] for r in records if r["kind"] == "train"] == [0.0]
    else:
        assert calls["broadcast"] == launches + 1 and calls["d2h"] >= calls["broadcast"]
    assert sum(r.get("n_refresh", 0) for r in records) >= launches // 2


def test_train_checkpoints_the_window_and_a_resume_continues_it(tmp_path, one_chip):
    """train() with the deployment's flags at a small size: a checkpoint's
    sidecar holds the window, and the resumed run takes no priming steps
    again (its books still close)."""
    from distributed_ddpg_tpu import checkpoint as ckpt_lib
    from distributed_ddpg_tpu.train import train_jax

    ckpt_dir = str(tmp_path / "ckpt")
    config = cfg(
        n_step=N, device_actor_chunk=2, learner_chunk=2, max_ingest_ratio=8.0, replay_min_size=64,
        warmup_uniform_steps=64, total_env_steps=64 + 16 + 16 * 12, eval_every=0, checkpoint_dir=ckpt_dir,
        checkpoint_every=8, log_path=str(tmp_path / "a.jsonl"),
    )
    first = train_jax(config)
    step = ckpt_lib.latest_step(ckpt_dir)
    sidecar = np.load(os.path.join(ckpt_dir, f"step_{step}", "devactor_carry.npz"))
    shapes = sorted(tuple(sidecar[k].shape) for k in sidecar.files)
    assert (E, N - 1, 2 * OBS + ACT + 3) in shapes and (E, N - 1) in shapes
    second = train_jax(config.replace(total_env_steps=2 * config.total_env_steps, log_path=str(tmp_path / "b.jsonl")))
    assert second["learner_steps"] > first["learner_steps"]
    assert second["ingest_queue_rows"] == (N - 1) * E
    assert second["buffer_fill"] + second["ingest_queue_rows"] == second["env_steps"]


def test_the_stand_in_has_no_host_worker_and_a_trainer_side_adapter():
    from distributed_ddpg_tpu.envs import make, spec_of

    with pytest.raises(ValueError, match="JAX dynamics only"):
        DDPGConfig(env_id=STAND_IN_ID)  # host workers cannot step it
    with pytest.raises(ValueError, match="JAX dynamics only"):
        cfg(num_actors=1)
    assert cfg(n_step=N).n_step == N  # the device backend folds n steps itself
    env = make(STAND_IN_ID, seed=4)
    spec = spec_of(env)
    assert (spec.obs_dim, spec.act_dim) == (108, 21)
    obs, _ = env.reset()
    nxt, reward, terminated, truncated, _ = env.step(np.zeros(21, np.float32))
    assert obs.shape == nxt.shape == (108,) and np.isfinite(reward) and not (terminated or truncated)


def test_ring_wraps_counts_passes_of_the_write_pointer():
    mesh = one_device_mesh()
    ring = DeviceReplay(64, 3, 1, mesh=mesh, block_size=16, async_ship=False)
    rows = jnp.ones((48, ring.width), jnp.float32)
    assert ring.ingest_snapshot()["ring_wraps"] == 0
    ring.insert_device_rows(rows)
    assert ring.ingest_snapshot()["ring_wraps"] == 0 and len(ring) == 48
    ring.insert_device_rows(rows)
    assert ring.ingest_snapshot()["ring_wraps"] == 1 and len(ring) == 64
    ring.insert_device_rows(rows)
    ring.insert_device_rows(rows)
    assert ring.ingest_snapshot()["ring_wraps"] == 3
