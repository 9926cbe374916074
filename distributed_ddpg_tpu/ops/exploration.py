"""Shared vectorized exploration + env-step body for the two on-device
rollout loops — the fused monolith (`ondevice.py`) and the device-actor
pool (`actors/device_pool.py`).

Both backends advance E vmapped JAX envs per scan iteration with the same
semantics: per-env OU noise (or SAC's on-device tanh-Gaussian sampling),
a = clip(mu(s) + ou * scale, bounds), optional uniform-warmup override,
vmapped `env.step` with auto-reset, and the packed transition rows in
`types.pack_batch_np` column order with the bootstrap discount folding
TRUE termination (`gamma * (1 - terminated)`; time-limit truncation keeps
bootstrapping — the jax_envs.StepOut contract). Keeping the body in one
place means an exploration fix or a wire-format change cannot silently
diverge the two backends; only the params source and the warmup-gate
basis (replay-ring fill vs the pool's own step counter) differ, and both
ride in as arguments.

PRNG discipline: the caller's `key` ALWAYS splits 4 ways
(next, ou/sac-sample, env, uniform) in this order, whether or not the
SAC/warmup branches consume their splits — that is what lets a
host-stepped parity reference (tests/test_device_actors.py) replay the
exact stream, and it keeps existing seeds' streams stable across both
backends.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_ddpg_tpu.models.mlp import actor_apply


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
):
    """One vectorized exploration step over `num_envs` envs.

    `warmup_active`: None = no uniform-warmup override compiled in
    (static off); else a traced bool[] — where True, actions are drawn
    uniformly from the action box instead of the policy (each backend
    supplies its own gate basis).

    Returns `(next_key, new_ou, action, out, rows)` where `out` is the
    vmapped StepOut, `new_ou` is the OU state with done envs reset to the
    mean, and `rows` is the packed f32[num_envs, D] transition block."""
    E = num_envs
    next_key, k_ou, k_env, k_uni = jax.random.split(key, 4)
    if cfg.sac:
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
    out = jax.vmap(env.step)(env_state, action, jax.random.split(k_env, E))
    # Packed rows in types.pack_batch_np order; discount 0 where the env
    # truly terminated, truncation keeps bootstrapping.
    discount = cfg.gamma * (
        1.0 - jnp.broadcast_to(out.terminated, (E,)).astype(jnp.float32)
    )
    rows = jnp.concatenate(
        [
            obs,
            action,
            out.reward[:, None],
            discount[:, None],
            out.boot_obs,
            jnp.ones((E, 1), jnp.float32),
        ],
        axis=-1,
    )
    new_ou = jnp.where(out.done[:, None], 0.0, new_ou)
    return next_key, new_ou, action, out, rows
