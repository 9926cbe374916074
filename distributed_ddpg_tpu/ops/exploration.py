"""The vectorized exploration + env-step body of the on-device rollout
loop, the device-actor pool (`actors/device_pool.py`).

The pool advances E vmapped JAX envs per scan iteration: per-env OU noise
(or SAC's on-device tanh-Gaussian sampling),
a = clip(mu(s) + ou * scale, bounds), optional uniform-warmup override,
vmapped `env.step` with auto-reset, and the packed transition rows in
`types.pack_batch_np` column order with the bootstrap discount folding
TRUE termination (`gamma * (1 - terminated)`; time-limit truncation keeps
bootstrapping — the jax_envs.StepOut contract). The body is apart from
the pool's episode accounting so that a host-stepped reference can call
it one step at a time; the params and the warmup gate ride in as
arguments.

PRNG discipline: the caller's `key` ALWAYS splits 4 ways
(next, ou/sac-sample, env, uniform) in this order, whether or not the
SAC/warmup branches consume their splits — that is what lets a
host-stepped parity reference (tests/test_device_actors.py) replay the
exact stream, and it keeps existing seeds' streams stable.
"""

from __future__ import annotations

import contextlib
from typing import NamedTuple

import jax
import jax.numpy as jnp

from distributed_ddpg_tpu.models.mlp import actor_apply
from distributed_ddpg_tpu.trace import device_scope


def sigma_ladder(cfg, num_envs: int):
    """PQL's mixed exploration (arXiv 2307.12983): environment i of E keeps
    its own fixed Gaussian scale, evenly spaced from explore_sigma_min
    (environment 0) to explore_sigma_max (environment E - 1): f32[E]."""
    return jnp.linspace(
        cfg.explore_sigma_min, cfg.explore_sigma_max, num_envs,
        dtype=jnp.float32,
    )


class NStepWindow(NamedTuple):
    """The n - 1 rows each environment has begun and not yet emitted,
    oldest first, in the packed row layout: a row's reward column holds the
    return folded so far, its discount column gamma^m over the m steps
    folded (0 once a step truly terminated), its next_obs the newest
    bootstrap observation."""

    rows: jnp.ndarray    # f32[E, n - 1, D]
    closed: jnp.ndarray  # bool[E, n - 1] the row's episode ended: it folds no more


def nstep_window(num_envs: int, n_step: int, width: int) -> NStepWindow:
    """The window before any step: every slot closed over zeros, rows that
    the pool's priming steps emit and drop (actors/device_pool.py)."""
    return NStepWindow(
        rows=jnp.zeros((num_envs, n_step - 1, width), jnp.float32),
        closed=jnp.ones((num_envs, n_step - 1), bool),
    )


def nstep_fold(window: NStepWindow, rows, out, gamma, obs_dim, act_dim):
    """One step of the n-step fold over E environments: the rows the host
    accumulator emits (replay/nstep.py with the worker's truncation flush),
    one a step and environment. `rows` are this step's packed 1-step rows
    and `out` its StepOut. Every open row of the window takes the step in
    (return += discount * reward, discount *= gamma, or 0 at a true
    termination, next_obs = the step's bootstrap observation) and closes
    where the episode ended; the oldest row leaves, n steps after it began
    whether it holds n steps or fewer, and this step's row joins.

    Returns (window, emitted f32[E, D], short bool[E]: the emitted row
    holds fewer than n steps)."""
    r_col = obs_dim + act_dim
    pend, closed = window.rows, window.closed
    disc = pend[..., r_col + 1]
    alive = 1.0 - out.terminated.astype(jnp.float32)
    taken = jnp.concatenate(
        [
            pend[..., :r_col],
            (pend[..., r_col] + disc * out.reward[:, None])[..., None],
            (disc * gamma * alive[:, None])[..., None],
            jnp.broadcast_to(
                out.boot_obs[:, None, :], (*closed.shape, obs_dim)
            ),
            pend[..., -1:],
        ],
        axis=-1,
    )
    pend = jnp.where(closed[..., None], pend, taken)
    every = jnp.concatenate([pend, rows[:, None, :]], axis=1)
    ended = jnp.concatenate(
        [closed | out.done[:, None], out.done[:, None]], axis=1
    )
    return (
        NStepWindow(rows=every[:, 1:], closed=ended[:, 1:]),
        every[:, 0],
        closed[:, 0],
    )


class SeqWindow(NamedTuple):
    """The last `count` <= L steps of each environment's CURRENT episode,
    left-aligned: step j of the window in slot j, its observation in obs
    slot j and the observation it led to in obs slot j + 1. Slots from
    `count` on (obs slots from `count` + 1 on) hold what an older episode
    left there, which `seq_fold` masks out of every row it emits."""

    obs: jnp.ndarray         # f32[E, L + 1, o]
    action: jnp.ndarray      # f32[E, L, a]
    reward: jnp.ndarray      # f32[E, L]
    terminated: jnp.ndarray  # f32[E, L]  1 where the step truly terminated
    count: jnp.ndarray       # i32[E]     steps held, 0 .. L


def seq_window(first_obs, steps: int, act_dim: int) -> SeqWindow:
    """The window before any step of episodes that begin at `first_obs`
    f32[E, o]: no step held, obs slot 0 the first observation."""
    num_envs, obs_dim = first_obs.shape
    obs = jnp.zeros((num_envs, steps + 1, obs_dim), jnp.float32)
    return SeqWindow(
        obs=obs.at[:, 0].set(first_obs),
        action=jnp.zeros((num_envs, steps, act_dim), jnp.float32),
        reward=jnp.zeros((num_envs, steps), jnp.float32),
        terminated=jnp.zeros((num_envs, steps), jnp.float32),
        count=jnp.zeros((num_envs,), jnp.int32),
    )


def seq_fold(window: SeqWindow, action, out):
    """One step of the window fold over E environments: the step (o_t, which
    the window already holds, `action`, out.reward, out.terminated,
    out.boot_obs) joins each environment's window, the oldest step leaving
    where L are held, and ONE row an environment is emitted (types.
    unpack_windows' layout: [o_0 .. o_L | a | r | d | m], time-major, zeros
    in every padded slot): the last <= L steps of the episode up to and
    with this one, left-aligned, m = 1 on the real ones. The row's o_{j+1}
    behind its newest step is the step's BOOTSTRAP observation (pre-reset:
    what a truncated episode's target bootstraps from). Where the episode
    ended (out.done) the window then empties and its obs slot 0 takes the
    new episode's first observation (out.obs).

    Returns (window, rows f32[E, D])."""
    steps = window.action.shape[1]
    full = window.count >= steps
    at = jnp.minimum(window.count, steps - 1)  # the slot this step takes

    def slid(x):  # the oldest step leaves where the window is full
        lead = full.reshape(-1, *([1] * (x.ndim - 1)))
        return jnp.where(lead, jnp.roll(x, -1, axis=1), x)

    def put(x, slot, value):  # x[e, slot[e]] = value[e]
        hit = jnp.arange(x.shape[1])[None, :] == slot[:, None]
        hit = hit.reshape(*hit.shape, *([1] * (x.ndim - 2)))
        return jnp.where(hit, value[:, None], x)

    obs = put(slid(window.obs), at + 1, out.boot_obs)
    action = put(slid(window.action), at, action)
    reward = put(slid(window.reward), at, out.reward)
    terminated = put(
        slid(window.terminated), at, out.terminated.astype(jnp.float32)
    )
    count = jnp.minimum(window.count + 1, steps)
    real = (jnp.arange(steps)[None, :] < count[:, None]).astype(jnp.float32)
    seen = (jnp.arange(steps + 1)[None, :] <= count[:, None]).astype(jnp.float32)
    num_envs = obs.shape[0]
    rows = jnp.concatenate(
        [
            (obs * seen[..., None]).reshape(num_envs, -1),
            (action * real[..., None]).reshape(num_envs, -1),
            reward * real,
            terminated * real,
            real,
        ],
        axis=-1,
    )
    done = out.done
    return (
        SeqWindow(
            obs=jnp.where(
                done[:, None, None], put(obs, jnp.zeros_like(at), out.obs), obs
            ),
            action=action,
            reward=reward,
            terminated=terminated,
            count=jnp.where(done, 0, count),
        ),
        rows,
    )


def vector_env_step(
    cfg,
    env,
    num_envs: int,
    params,
    env_state,
    obs,
    ou,
    key,
    scale,
    offset,
    low,
    high,
    warmup_active=None,
    mean_action=None,
):
    """One vectorized exploration step over `num_envs` envs.

    `mean_action`: the policy's action where the caller has computed it (a
    recurrent policy's one step, which owns a memory: the pool's); the
    Gaussian ladder's noise is added to it and no policy is applied here.

    `warmup_active`: None = no uniform-warmup override compiled in
    (static off); else a traced bool[] — where True, actions are drawn
    uniformly from the action box instead of the policy (the pool gates on
    its own cumulative step counter).

    Returns `(next_key, new_ou, action, out, rows)` where `out` is the
    vmapped StepOut, `new_ou` is the OU state with done envs reset to the
    mean, and `rows` is the packed f32[num_envs, D] transition block."""
    E = num_envs
    next_key, k_ou, k_env, k_uni = jax.random.split(key, 4)
    with device_scope("policy"):
        if cfg.pixels:
            # DrQ-v2: the convolutional policy on the byte frames, and noise
            # of the ONE scheduled scale at the newest learner step the
            # pool was handed (`params["learner_step"]`), unclipped but for
            # the action box, as the source samples when it acts.
            from distributed_ddpg_tpu.models.pixels import policy_apply
            from distributed_ddpg_tpu.ops.pixels import sigma_at

            sigma = sigma_at(cfg.sigma_schedule, params["learner_step"])
            action = jnp.clip(
                policy_apply(params, obs, scale, offset)
                + sigma * jax.random.normal(k_ou, ou.shape, jnp.float32) * scale,
                low,
                high,
            )
            new_ou = ou
        elif cfg.sac:
            # SAC explores by sampling its own tanh-Gaussian on device; the
            # OU state rides along untouched (zeros — worker.py parity).
            from distributed_ddpg_tpu.models.mlp import actor_gaussian_apply
            from distributed_ddpg_tpu.ops import losses as losses_lib

            mean, log_std = actor_gaussian_apply(
                params, obs, cfg.sac_log_std_min, cfg.sac_log_std_max
            )
            sampled, _ = losses_lib.sac_sample(
                mean, log_std, jax.random.normal(k_ou, mean.shape), scale, offset
            )
            action = jnp.clip(sampled, low, high)
            new_ou = ou
        elif cfg.exploration == "gaussian":
            # PQL's ladder: a fixed scale per environment, no state between
            # steps (the OU state rides along untouched).
            action = jnp.clip(
                (
                    actor_apply(params, obs, scale, offset)
                    if mean_action is None else mean_action
                )
                + sigma_ladder(cfg, E)[:, None]
                * jax.random.normal(k_ou, ou.shape, jnp.float32)
                * scale,
                low,
                high,
            )
            new_ou = ou
        else:
            new_ou = (
                ou
                + cfg.ou_theta * (0.0 - ou) * cfg.ou_dt
                + cfg.ou_sigma
                * jnp.sqrt(cfg.ou_dt)
                * jax.random.normal(k_ou, ou.shape, jnp.float32)
            )
            action = jnp.clip(
                actor_apply(params, obs, scale, offset) + new_ou * scale,
                low,
                high,
            )
        if warmup_active is not None:
            action = jnp.where(
                warmup_active,
                jax.random.uniform(
                    k_uni, action.shape, jnp.float32, minval=low, maxval=high
                ),
                action,
            )
    # An environment that brackets its own parts (a pixel one: `env` and
    # `render`) is not bracketed again.
    with (
        contextlib.nullcontext() if getattr(env, "scopes_itself", False)
        else device_scope("env")
    ):
        out = jax.vmap(env.step)(
            env_state, action, jax.random.split(k_env, E)
        )
    # Packed rows in types.pack_batch_np order; discount 0 where the env
    # truly terminated, truncation keeps bootstrapping. Byte frames ride
    # the float32 row as words, bitcast and never computed on
    # (ops/pixels.py); `out.boot_obs` leaves as those words too, which is
    # what the n-step fold writes into the rows it holds.
    discount = cfg.gamma * (
        1.0 - jnp.broadcast_to(out.terminated, (E,)).astype(jnp.float32)
    )
    boot_obs = out.boot_obs
    if obs.dtype == jnp.uint8:
        from distributed_ddpg_tpu.ops.pixels import words_of

        obs, boot_obs = words_of(obs), words_of(boot_obs)
        out = out._replace(boot_obs=boot_obs)
    rows = jnp.concatenate(
        [
            obs,
            action,
            out.reward[:, None],
            discount[:, None],
            boot_obs,
            jnp.ones((E, 1), jnp.float32),
        ],
        axis=-1,
    )
    new_ou = jnp.where(out.done[:, None], 0.0, new_ou)
    return next_key, new_ou, action, out, rows
