"""On-device (JAX) environments — the TPU-native extension of SURVEY.md §1's
'Environment' row.

The reference (and the `jax_tpu` backend here) steps CPU envs in worker
processes. For envs whose dynamics are a few FLOPs of arithmetic, that
topology leaves the accelerator idle between batches; these implementations
express the dynamics as pure JAX functions so the rollout — policy forward,
exploration noise, env physics — compiles into one XLA program
(actors/device_pool.py) whose rows land in the replay ring without leaving
the device, and the fused beat (parallel/megastep.py) puts the insert and
the learner's chunk in the same program. vmap supplies the
batch dimension: one `step` call advances E envs in lockstep on the MXU/VPU.

API (functional, scan/vmap-friendly; no Python state):
  env.init(key)            -> state pytree (single env)
  env.step(state, u, key)  -> StepOut(state, obs, boot_obs, reward, done)
                              with AUTO-RESET: when an episode ends, `state`
                              is already the reset state and `obs` its first
                              observation (what the policy acts on next),
                              while `boot_obs` is the PRE-reset next
                              observation — the correct bootstrap target for
                              the stored transition (time-limit truncation
                              keeps bootstrapping; conflating the two would
                              bootstrap across the episode boundary).
  env.observe(state)       -> obs

JaxPendulum mirrors the builtin numpy Pendulum (envs/pendulum.py) equation
for equation — g=10, m=1, l=1, dt=0.05, max_torque=2, max_speed=8,
200-step time limit — asserted by tests/test_jax_env.py, so `Pendulum-v1`
results are comparable across all three backends.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class StepOut(NamedTuple):
    state: object             # post-step state (reset already applied if done)
    obs: jnp.ndarray          # observation of `state` (policy input)
    boot_obs: jnp.ndarray     # pre-reset next observation (replay next_obs)
    reward: jnp.ndarray       # f32[]
    done: jnp.ndarray         # bool[] episode boundary (truncation included)
    # bool[] TRUE termination (env reached an absorbing state): bootstrap
    # discount is 0. Time-limit truncation keeps done=True, terminated=False
    # and keeps bootstrapping. Pendulum only truncates; MountainCar also
    # terminates at the goal.
    terminated: jnp.ndarray


class PendulumState(NamedTuple):
    th: jnp.ndarray       # f32[] angle
    thdot: jnp.ndarray    # f32[] angular velocity
    t: jnp.ndarray        # i32[] step-in-episode counter


def _angle_normalize(x):
    return ((x + jnp.pi) % (2 * jnp.pi)) - jnp.pi


class JaxPendulum:
    """Pendulum-v1 dynamics as pure JAX (see module docstring)."""

    max_speed = 8.0
    max_torque = 2.0
    dt = 0.05
    g = 10.0
    m = 1.0
    l = 1.0
    max_episode_steps = 200

    obs_dim = 3
    act_dim = 1
    action_low = np.array([-2.0], np.float32)
    action_high = np.array([2.0], np.float32)

    def init(self, key) -> PendulumState:
        high = jnp.array([jnp.pi, 1.0], jnp.float32)
        th, thdot = jax.random.uniform(key, (2,), jnp.float32, -high, high)
        return PendulumState(th=th, thdot=thdot, t=jnp.zeros((), jnp.int32))

    def observe(self, s: PendulumState) -> jnp.ndarray:
        return jnp.stack([jnp.cos(s.th), jnp.sin(s.th), s.thdot]).astype(jnp.float32)

    def step(self, s: PendulumState, action, key):
        u = jnp.clip(action.reshape(())[None], -self.max_torque, self.max_torque)[0]
        cost = (
            _angle_normalize(s.th) ** 2 + 0.1 * s.thdot**2 + 0.001 * u**2
        )
        newthdot = s.thdot + (
            3.0 * self.g / (2.0 * self.l) * jnp.sin(s.th)
            + 3.0 / (self.m * self.l**2) * u
        ) * self.dt
        newthdot = jnp.clip(newthdot, -self.max_speed, self.max_speed)
        newth = s.th + newthdot * self.dt
        t = s.t + 1
        done = t >= self.max_episode_steps
        stepped = PendulumState(th=newth, thdot=newthdot, t=t)
        # Auto-reset: where the time limit hit, the next state is a fresh
        # episode start (same distribution as init).
        fresh = self.init(key)
        nxt = PendulumState(
            th=jnp.where(done, fresh.th, newth),
            thdot=jnp.where(done, fresh.thdot, newthdot),
            t=jnp.where(done, fresh.t, t),
        )
        return StepOut(
            state=nxt,
            obs=self.observe(nxt),
            boot_obs=self.observe(stepped),
            reward=-cost.astype(jnp.float32),
            done=done,
            terminated=jnp.zeros((), bool),  # Pendulum only truncates
        )


class MountainCarState(NamedTuple):
    pos: jnp.ndarray      # f32[] position
    vel: jnp.ndarray      # f32[] velocity
    t: jnp.ndarray        # i32[] step-in-episode counter


class JaxMountainCar:
    """MountainCarContinuous-v0 dynamics as pure JAX, equation for equation
    with gymnasium's continuous_mountain_car (power=0.0015, gravity term
    0.0025*cos(3x), goal at x>=0.45 with vel>=0, +100 terminal reward,
    -0.1*a^2 action cost, 999-step time limit) — asserted against the real
    gymnasium env by tests/test_jax_envs.py. Unlike Pendulum this env truly
    TERMINATES, exercising the terminated/truncated split end to end."""

    power = 0.0015
    gravity = 0.0025
    min_position = -1.2
    max_position = 0.6
    max_speed = 0.07
    goal_position = 0.45
    goal_velocity = 0.0
    max_episode_steps = 999

    obs_dim = 2
    act_dim = 1
    action_low = np.array([-1.0], np.float32)
    action_high = np.array([1.0], np.float32)

    def init(self, key) -> MountainCarState:
        pos = jax.random.uniform(key, (), jnp.float32, -0.6, -0.4)
        return MountainCarState(
            pos=pos, vel=jnp.zeros((), jnp.float32), t=jnp.zeros((), jnp.int32)
        )

    def observe(self, s: MountainCarState) -> jnp.ndarray:
        return jnp.stack([s.pos, s.vel]).astype(jnp.float32)

    def step(self, s: MountainCarState, action, key):
        force = jnp.clip(action.reshape(())[None], -1.0, 1.0)[0]
        vel = s.vel + force * self.power - self.gravity * jnp.cos(3.0 * s.pos)
        vel = jnp.clip(vel, -self.max_speed, self.max_speed)
        pos = jnp.clip(s.pos + vel, self.min_position, self.max_position)
        vel = jnp.where((pos <= self.min_position) & (vel < 0.0), 0.0, vel)
        t = s.t + 1
        terminated = (pos >= self.goal_position) & (vel >= self.goal_velocity)
        done = terminated | (t >= self.max_episode_steps)
        reward = jnp.where(terminated, 100.0, 0.0) - 0.1 * force**2
        stepped = MountainCarState(pos=pos, vel=vel, t=t)
        fresh = self.init(key)
        nxt = MountainCarState(
            pos=jnp.where(done, fresh.pos, pos),
            vel=jnp.where(done, fresh.vel, vel),
            t=jnp.where(done, fresh.t, t),
        )
        return StepOut(
            state=nxt,
            obs=self.observe(nxt),
            boot_obs=self.observe(stepped),
            reward=reward.astype(jnp.float32),
            done=done,
            terminated=terminated,
        )


class StandInState(NamedTuple):
    x: jnp.ndarray        # f32[obs_dim] the state, which is the observation
    t: jnp.ndarray        # i32[] step-in-episode counter


class IsaacHumanoidStandIn:
    """A STAND-IN for Isaac Gym's Humanoid, at its interface and nothing
    more (obs 108, act 21, actions in [-1, 1], 1,000-step limit): Isaac Gym
    cannot run here and `mujoco.mjx` is not installed, so the dynamics are a
    seeded synthetic control system, x' = A x + B u + noise, with A a fixed
    random rotation scaled by `RHO` < 1 (stable: every direction decays at
    the same rate, so the inputs accumulate), B a fixed random input map of
    spectral norm `INPUT_GAIN` and the noise drawn from the step's key. The reward is an alive
    bonus less a quadratic cost on state and action; the episode TERMINATES
    when any state component leaves the box |x_i| <= `BOX` (no bootstrap) and
    is TRUNCATED at the step limit (bootstrap from the pre-reset
    observation), so a replay fed by it sees both kinds of episode end.
    A and B come from `MATRIX_SEED`, a constant of the environment, never
    from a run's seed: every run steps the same system. One step is two
    matrix-vector products, ~28 kFLOP: far below a physics step's cost, so a
    run on this environment leaves the simulator's share of the device out
    (docs/DEVICE_ACTORS.md)."""

    obs_dim = 108
    act_dim = 21
    max_episode_steps = 1000
    action_low = np.full((21,), -1.0, np.float32)
    action_high = np.full((21,), 1.0, np.float32)

    MATRIX_SEED = 0x15AAC
    RHO = 0.97           # every singular value of A
    INPUT_GAIN = 0.3     # spectral norm of B: uniform random actions end
    #                      about two episodes in three by termination
    NOISE = 0.02         # process noise, per component and step
    BOX = 1.0            # |x_i| beyond it ends the episode for good
    INIT = 0.1           # x_0 ~ U(-INIT, INIT)
    ALIVE = 1.0
    STATE_COST = 2.0     # on mean(x^2)
    ACTION_COST = 0.05   # on mean(u^2)

    def __init__(self):
        # the state's size: the observation's here, a subclass may differ
        n = getattr(self, "state_dim", self.obs_dim)
        rng = np.random.RandomState(self.MATRIX_SEED)
        a, _ = np.linalg.qr(rng.standard_normal((n, n)))
        b = rng.standard_normal((self.act_dim, n))
        self.a = (self.RHO * a).astype(np.float32)
        self.b = (self.INPUT_GAIN * b / np.linalg.norm(b, 2)).astype(np.float32)

    def init(self, key) -> StandInState:
        x = jax.random.uniform(
            key, (self.obs_dim,), jnp.float32, -self.INIT, self.INIT
        )
        return StandInState(x=x, t=jnp.zeros((), jnp.int32))

    def observe(self, s: StandInState) -> jnp.ndarray:
        return s.x

    def step(self, s: StandInState, action, key):
        u = jnp.clip(action, -1.0, 1.0)
        k_noise, k_reset = jax.random.split(key)
        x = (
            s.x @ self.a + u @ self.b
            + self.NOISE * jax.random.normal(k_noise, s.x.shape, jnp.float32)
        )
        reward = (
            self.ALIVE
            - self.STATE_COST * jnp.mean(jnp.square(x))
            - self.ACTION_COST * jnp.mean(jnp.square(u))
        )
        t = s.t + 1
        terminated = jnp.max(jnp.abs(x)) > self.BOX
        done = terminated | (t >= self.max_episode_steps)
        stepped = StandInState(x=x, t=t)
        fresh = self.init(k_reset)
        nxt = StandInState(
            x=jnp.where(done, fresh.x, x), t=jnp.where(done, fresh.t, t)
        )
        return StepOut(
            state=nxt,
            obs=self.observe(nxt),
            boot_obs=self.observe(stepped),
            reward=reward.astype(jnp.float32),
            done=done,
            terminated=terminated,
        )


class OccludedHumanoidStandIn(IsaacHumanoidStandIn):
    """IsaacHumanoidStandIn with half its state hidden: the system's state
    has 108 components and the observation is the FIRST 54 of them, the
    way the "-P" wrappers of arXiv 2110.05038's standard-POMDP tasks keep
    the positions and hide the velocities (there on PyBullet's tasks, here
    on a stand-in: no physics step). A is a rotation of the whole state, so
    what the hidden half holds reaches the seen half a step later, and the
    reward and the termination box read all 108: the state a policy needs
    is not in one observation. Dynamics, reward, box, limits and
    `MATRIX_SEED` are the parent's, so every run steps the same system as
    PQL's cell does and sees half of it."""

    obs_dim = 54
    state_dim = 108

    def init(self, key) -> StandInState:
        x = jax.random.uniform(
            key, (self.state_dim,), jnp.float32, -self.INIT, self.INIT
        )
        return StandInState(x=x, t=jnp.zeros((), jnp.int32))

    def observe(self, s: StandInState) -> jnp.ndarray:
        return s.x[: self.obs_dim]


class PixelStandInState(NamedTuple):
    x: jnp.ndarray        # f32[108] the system's state, which no policy sees
    t: jnp.ndarray        # i32[] agent steps into the episode
    frames: jnp.ndarray   # uint8[9, 84, 84] the last three rendered frames


class PixelHumanoidStandIn(IsaacHumanoidStandIn):
    """A STAND-IN for DeepMind Control's humanoid FROM PIXELS as DrQ-v2 runs
    it (arXiv 2107.09645), at its interface and nothing more: observations
    uint8[9, 84, 84] (three stacked RGB frames, the first frame three times
    after a reset), 21 actions in [-1, 1], action repeat 2 inside `step`
    (the same action twice, the rewards summed, the second sub-step skipped
    where the first ended the episode), 500 agent steps an episode. dm_control
    and its renderer cannot run here, so BOTH halves are stand-ins:

    - the dynamics are IsaacHumanoidStandIn's seeded linear system (its A, B,
      noise, box, reward), at 21 actions: no physics step;
    - the renderer is a fixed smooth map of the state, no rasteriser: plane c
      of a frame is 127.5 + 127.5 * mean_k tanh(U_ck x + a_ck)_i
      tanh(V_ck x + b_ck)_j, rounded to a byte, with U, V, a, b drawn once
      from `MATRIX_SEED` (`RENDER_RANK` = 8 terms a plane). Every pixel moves
      with the state, so frames differ from step to step and from
      environment to environment and a convolution's gradient on them is not
      degenerate; a frame costs ~1.2 MFLOP (two [8 x 84, 108] projections a
      plane and an [84, 8] @ [8, 84] product), far below a rasteriser's.

    The simulator's and the renderer's share of the device are MISSING from a
    run on this environment (docs/DEVICE_ACTORS.md), as PQL's simulator is.
    `step` brackets its own parts (`env`, `render`: trace.ROLLOUT_SCOPES)."""

    obs_shape = (9, 84, 84)
    obs_dtype = "uint8"
    obs_dim = 9 * 84 * 84   # elements of one observation
    state_dim = 108
    max_episode_steps = 500  # agent steps: 1,000 frames at action repeat 2
    ACTION_REPEAT = 2
    SIDE = 84
    RENDER_RANK = 8
    scopes_itself = True

    def __init__(self):
        super().__init__()
        rng = np.random.RandomState(self.MATRIX_SEED ^ 0xF4A3E)
        shape = (3, self.RENDER_RANK, self.SIDE)
        self.render_u, self.render_v = (
            rng.standard_normal((*shape, self.state_dim)).astype(np.float32)
            for _ in range(2)
        )
        self.render_a, self.render_b = (
            rng.uniform(-2.0, 2.0, shape).astype(np.float32) for _ in range(2)
        )

    def render(self, x) -> jnp.ndarray:
        """One frame uint8[3, 84, 84] of state x."""
        rows = jnp.tanh(self.render_u @ x + self.render_a)   # [3, K, 84]
        cols = jnp.tanh(self.render_v @ x + self.render_b)
        plane = jnp.einsum("cki,ckj->cij", rows, cols) / self.RENDER_RANK
        return jnp.round(127.5 + 127.5 * plane).astype(jnp.uint8)

    def init(self, key) -> PixelStandInState:
        x = jax.random.uniform(
            key, (self.state_dim,), jnp.float32, -self.INIT, self.INIT
        )
        return PixelStandInState(
            x=x, t=jnp.zeros((), jnp.int32),
            frames=jnp.tile(self.render(x), (3, 1, 1)),
        )

    def observe(self, s: PixelStandInState) -> jnp.ndarray:
        return s.frames

    def step(self, s: PixelStandInState, action, key):
        # the words of trace.ROLLOUT_SCOPES (`rollout/env`, `rollout/render`):
        # this layer imports nothing of the package, so it names them itself
        device_scope = jax.named_scope
        u = jnp.clip(action, -1.0, 1.0)
        k_noise, k_reset = jax.random.split(key)
        with device_scope("env"):
            x, reward = s.x, jnp.zeros((), jnp.float32)
            terminated = jnp.zeros((), bool)
            for k in jax.random.split(k_noise, self.ACTION_REPEAT):
                nx = (
                    x @ self.a + u @ self.b
                    + self.NOISE * jax.random.normal(k, x.shape, jnp.float32)
                )
                r = (
                    self.ALIVE
                    - self.STATE_COST * jnp.mean(jnp.square(nx))
                    - self.ACTION_COST * jnp.mean(jnp.square(u))
                )
                reward = reward + jnp.where(terminated, 0.0, r)
                x = jnp.where(terminated, x, nx)
                terminated = terminated | (jnp.max(jnp.abs(x)) > self.BOX)
                # (a sub-step behind the episode's end changes nothing)
            t = s.t + 1
            done = terminated | (t >= self.max_episode_steps)
            fresh_x = jax.random.uniform(
                k_reset, (self.state_dim,), jnp.float32, -self.INIT, self.INIT
            )
        with device_scope("render"):
            # two renders a step, whichever is kept: the stepped state's
            # frame (the bootstrap observation, also of an episode that
            # ended) and a fresh episode's first
            stepped = jnp.concatenate([s.frames[3:], self.render(x)])
            first = jnp.tile(self.render(fresh_x), (3, 1, 1))
        nxt = PixelStandInState(
            x=jnp.where(done, fresh_x, x),
            t=jnp.where(done, 0, t),
            frames=jnp.where(done, first, stepped),
        )
        return StepOut(
            state=nxt,
            obs=nxt.frames,
            boot_obs=stepped,
            reward=reward.astype(jnp.float32),
            done=done,
            terminated=terminated,
        )


STAND_IN_ID = "IsaacHumanoidStandIn-v0"
PIXEL_STAND_IN_ID = "PixelHumanoidStandIn-v0"
OCCLUDED_STAND_IN_ID = "OccludedHumanoidStandIn-v0"

_JAX_ENVS = {
    STAND_IN_ID: IsaacHumanoidStandIn,
    PIXEL_STAND_IN_ID: PixelHumanoidStandIn,
    OCCLUDED_STAND_IN_ID: OccludedHumanoidStandIn,
    "Pendulum-v1": JaxPendulum,
    "builtin/Pendulum-v1": JaxPendulum,
    "MountainCarContinuous-v0": JaxMountainCar,
    "builtin/MountainCarContinuous-v0": JaxMountainCar,
}


def has_jax_env(env_id: str) -> bool:
    return env_id in _JAX_ENVS


def make_jax_env(env_id: str):
    if env_id not in _JAX_ENVS:
        raise ValueError(
            f"no on-device (JAX) implementation for {env_id!r}; available: "
            f"{sorted(set(_JAX_ENVS))} — use --backend=jax_tpu for CPU envs"
        )
    return _JAX_ENVS[env_id]()
