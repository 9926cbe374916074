"""On-device environments (envs/jax_envs.py), which the device-actor pool
steps (actors/device_pool.py).

- Dynamics equivalence: JaxPendulum must reproduce the builtin numpy
  Pendulum (envs/pendulum.py) step-for-step from the same state/actions —
  the guarantee that `Pendulum-v1` results compare across actor backends;
  JaxMountainCar and the builtin MountainCar against gymnasium's.
- Auto-reset semantics: boundary flags, boot_obs vs post-reset obs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ddpg_tpu.envs.jax_envs import JaxPendulum, make_jax_env
from distributed_ddpg_tpu.envs.pendulum import Pendulum


def test_jax_pendulum_matches_numpy_dynamics():
    from distributed_ddpg_tpu.envs.jax_envs import PendulumState

    jenv, nenv = JaxPendulum(), Pendulum(seed=0)
    nenv.reset(seed=3)
    th, thdot = nenv._state
    state = PendulumState(
        th=jnp.float32(th), thdot=jnp.float32(thdot), t=jnp.int32(0)
    )
    rng = np.random.default_rng(7)
    key = jax.random.PRNGKey(1)
    for i in range(60):
        a = rng.uniform(-2, 2, 1).astype(np.float32)
        key, k = jax.random.split(key)
        out = jenv.step(state, jnp.asarray(a), k)
        nobs, nrew, _, ntrunc, _ = nenv.step(a)
        assert not ntrunc
        np.testing.assert_allclose(np.asarray(out.obs), nobs, atol=1e-4)
        np.testing.assert_allclose(float(out.reward), nrew, atol=1e-4)
        assert not bool(out.done)
        state = out.state


def test_jax_pendulum_autoreset():
    env = JaxPendulum()
    key = jax.random.PRNGKey(0)
    state = env.init(key)
    state = state._replace(t=jnp.int32(env.max_episode_steps - 1))
    out = env.step(state, jnp.zeros(1), jax.random.PRNGKey(42))
    assert bool(out.done)
    assert int(out.state.t) == 0                       # fresh episode
    # boot_obs is the PRE-reset observation, obs the post-reset one.
    assert not np.allclose(np.asarray(out.obs), np.asarray(out.boot_obs))


def test_make_jax_env_unknown():
    with pytest.raises(ValueError, match="no on-device"):
        make_jax_env("HalfCheetah-v4")


def test_jax_mountain_car_matches_gymnasium_dynamics():
    gymnasium = pytest.importorskip("gymnasium")
    from distributed_ddpg_tpu.envs.jax_envs import JaxMountainCar, MountainCarState

    genv = gymnasium.make("MountainCarContinuous-v0")
    gobs, _ = genv.reset(seed=5)
    jenv = JaxMountainCar()
    state = MountainCarState(
        pos=jnp.float32(gobs[0]), vel=jnp.float32(gobs[1]), t=jnp.int32(0)
    )
    rng = np.random.default_rng(11)
    key = jax.random.PRNGKey(2)
    for i in range(80):
        a = rng.uniform(-1, 1, 1).astype(np.float32)
        key, k = jax.random.split(key)
        out = jenv.step(state, jnp.asarray(a), k)
        gobs, grew, gterm, gtrunc, _ = genv.step(a)
        assert not (gterm or gtrunc)
        np.testing.assert_allclose(np.asarray(out.obs), gobs, atol=1e-5)
        np.testing.assert_allclose(float(out.reward), grew, atol=1e-5)
        assert not bool(out.done)
        state = out.state


def test_builtin_mountain_car_matches_gymnasium():
    gymnasium = pytest.importorskip("gymnasium")
    from distributed_ddpg_tpu.envs.mountain_car import MountainCarContinuous

    genv = gymnasium.make("MountainCarContinuous-v0")
    gobs, _ = genv.reset(seed=5)
    benv = MountainCarContinuous(seed=0)
    benv.reset(seed=0)
    benv._pos, benv._vel = float(gobs[0]), float(gobs[1])
    rng = np.random.default_rng(11)
    for _ in range(80):
        a = rng.uniform(-1, 1, 1).astype(np.float32)
        bobs, brew, bterm, btrunc, _ = benv.step(a)
        gobs, grew, gterm, gtrunc, _ = genv.step(a)
        np.testing.assert_allclose(bobs, gobs, atol=1e-6)
        np.testing.assert_allclose(brew, grew, atol=1e-6)
        assert (bterm, btrunc) == (gterm, gtrunc)


def test_jax_mountain_car_terminates_at_goal():
    from distributed_ddpg_tpu.envs.jax_envs import JaxMountainCar, MountainCarState

    env = JaxMountainCar()
    state = MountainCarState(
        pos=jnp.float32(0.449), vel=jnp.float32(0.05), t=jnp.int32(10)
    )
    out = env.step(state, jnp.ones(1), jax.random.PRNGKey(3))
    assert bool(out.terminated) and bool(out.done)
    assert float(out.reward) == pytest.approx(100.0 - 0.1)
    assert int(out.state.t) == 0                       # auto-reset happened
    assert float(out.boot_obs[0]) >= env.goal_position  # pre-reset next obs
    assert -0.6 <= float(out.obs[0]) <= -0.4            # fresh start
