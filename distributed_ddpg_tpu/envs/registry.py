"""Environment registry (SURVEY.md §1 'Environment' row).

`make(env_id, seed)` resolves, in order:
1. built-in pure-numpy envs (zero-dependency: Pendulum);
2. gymnasium, if importable (covers the BASELINE.json ladder:
   LunarLanderContinuous, BipedalWalker, HalfCheetah, Humanoid).

Everything downstream (actors, replay, learner) only sees the EnvSpec +
the gymnasium 5-tuple step API, so new env sources plug in here.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from distributed_ddpg_tpu.envs.mountain_car import MountainCarContinuous
from distributed_ddpg_tpu.envs.pendulum import Pendulum

_BUILTIN = {
    "Pendulum-v1": Pendulum,
    "builtin/Pendulum-v1": Pendulum,
    "MountainCarContinuous-v0": MountainCarContinuous,
    "builtin/MountainCarContinuous-v0": MountainCarContinuous,
}

# Gymnasium retires env versions (DeprecatedEnv); keep the BASELINE.md ladder
# ids working by bumping to the successor when the pinned version is gone.
_VERSION_ALIASES = {
    "LunarLanderContinuous-v2": "LunarLanderContinuous-v3",
}


# Ids with JAX dynamics only (envs/jax_envs.py): `make` wraps them for the
# trainer's own use (the spec, an evaluation); a worker process, which must
# never import JAX, cannot step them (config.py refuses that at parse).
DEVICE_ONLY = frozenset({
    "IsaacHumanoidStandIn-v0", "PixelHumanoidStandIn-v0",
    "OccludedHumanoidStandIn-v0",
})


class EnvSpec(NamedTuple):
    obs_dim: int
    act_dim: int
    action_low: np.ndarray
    action_high: np.ndarray
    # An observation that is no flat float vector (byte frames) names its
    # shape and dtype; types.ObsSpec.of_env reads either kind.
    obs_shape: tuple = ()
    obs_dtype: str = "float32"

    @property
    def action_scale(self) -> np.ndarray:
        """Symmetric bound for tanh squashing (classic DDPG assumes
        symmetric action spaces; asymmetric spaces use scale+offset)."""
        return ((self.action_high - self.action_low) / 2.0).astype(np.float32)

    @property
    def action_offset(self) -> np.ndarray:
        return ((self.action_high + self.action_low) / 2.0).astype(np.float32)


class _GymnasiumAdapter:
    """Wraps a gymnasium env; normalizes seeding and exposes spec fields."""

    def __init__(self, env_id: str, seed: int = 0):
        import gymnasium

        self._env = gymnasium.make(env_id)
        self._seed = seed
        self._first_reset = True

    def reset(self, seed: int | None = None):
        if seed is None and self._first_reset:
            seed = self._seed
        self._first_reset = False
        return self._env.reset(seed=seed)

    def step(self, action):
        return self._env.step(np.asarray(action, np.float32))

    @property
    def observation_dim(self) -> int:
        return int(np.prod(self._env.observation_space.shape))

    @property
    def action_dim(self) -> int:
        return int(np.prod(self._env.action_space.shape))

    @property
    def action_low(self) -> np.ndarray:
        return np.asarray(self._env.action_space.low, np.float32)

    @property
    def action_high(self) -> np.ndarray:
        return np.asarray(self._env.action_space.high, np.float32)

    def close(self):
        self._env.close()


class _JaxEnvAdapter:
    """A functional JAX env (envs/jax_envs.py) behind the gymnasium 5-tuple
    API, stepped on the host's CPU backend whatever the default device is."""

    def __init__(self, env_id: str, seed: int = 0):
        import jax

        from distributed_ddpg_tpu.envs.jax_envs import make_jax_env

        self._jax = jax
        self._env = make_jax_env(env_id)
        self._cpu = jax.devices("cpu")[0]
        self._step = jax.jit(self._env.step)
        with jax.default_device(self._cpu):
            self._key = jax.random.PRNGKey(seed)
        self._state = None
        self.observation_dim = self._env.obs_dim
        self.observation_shape = tuple(getattr(self._env, "obs_shape", ()))
        self.observation_dtype = getattr(self._env, "obs_dtype", "float32")
        self.action_dim = self._env.act_dim
        self.action_low = np.asarray(self._env.action_low, np.float32)
        self.action_high = np.asarray(self._env.action_high, np.float32)

    def _next_key(self):
        self._key, sub = self._jax.random.split(self._key)
        return sub

    def reset(self, seed: int | None = None):
        with self._jax.default_device(self._cpu):
            if seed is not None:
                self._key = self._jax.random.PRNGKey(seed)
            self._state = self._env.init(self._next_key())
            return np.asarray(self._env.observe(self._state)), {}

    def step(self, action):
        with self._jax.default_device(self._cpu):
            out = self._step(
                self._state, np.asarray(action, np.float32), self._next_key()
            )
        # Auto-reset has already happened inside `out.state`: the caller
        # resets at an episode's end, as with any gymnasium env.
        self._state = out.state
        terminated = bool(out.terminated)
        return (
            np.asarray(out.boot_obs), float(out.reward), terminated,
            bool(out.done) and not terminated, {},
        )

    def close(self):
        pass


def make(env_id: str, seed: int = 0, prefer_builtin: bool = False):
    if env_id in DEVICE_ONLY:
        return _JaxEnvAdapter(env_id, seed=seed)
    if env_id in _BUILTIN and (prefer_builtin or not _has_gymnasium()):
        return _BUILTIN[env_id](seed=seed)
    if _has_gymnasium():
        try:
            return _GymnasiumAdapter(env_id, seed=seed)
        except Exception:
            if env_id in _VERSION_ALIASES:
                return _GymnasiumAdapter(_VERSION_ALIASES[env_id], seed=seed)
            if env_id in _BUILTIN:
                return _BUILTIN[env_id](seed=seed)
            raise
    if env_id in _BUILTIN:
        return _BUILTIN[env_id](seed=seed)
    raise ValueError(
        f"Unknown env {env_id!r}: not a builtin and gymnasium is unavailable"
    )


def _has_gymnasium() -> bool:
    try:
        import gymnasium  # noqa: F401

        return True
    except ImportError:
        return False


def spec_of(env) -> EnvSpec:
    return EnvSpec(
        obs_dim=int(env.observation_dim),
        act_dim=int(env.action_dim),
        action_low=np.asarray(env.action_low, np.float32),
        action_high=np.asarray(env.action_high, np.float32),
        obs_shape=tuple(getattr(env, "observation_shape", ())),
        obs_dtype=getattr(env, "observation_dtype", "float32"),
    )
