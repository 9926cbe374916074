"""Core pytree types: transitions, batches, and the learner TrainState.

The reference keeps its state scattered across TF graph variables on the
parameter server (SURVEY.md §1 'Distribution/comm'); here everything the
learner owns is ONE explicit pytree so the whole train step — losses, Adam,
Polyak — jits into a single XLA program with no host round trips
(SURVEY.md §3.3/§3.4).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import numpy as np

from distributed_ddpg_tpu.trace import device_scope


class Batch(NamedTuple):
    """A replay minibatch. `discount` already folds gamma^n * (1 - done) for
    n-step returns (D4PG), so the TD target is `r + discount * Q'(s', mu'(s'))`."""

    obs: Any          # f32[B, obs_dim]
    action: Any       # f32[B, act_dim]
    reward: Any       # f32[B]     (n-step discounted sum)
    discount: Any     # f32[B]     (gamma^n * (1 - done))
    next_obs: Any     # f32[B, obs_dim]
    weight: Any       # f32[B]     (PER importance weights; ones if uniform)


class OptState(NamedTuple):
    """Adam state for one parameter tree (matches optax.adam semantics)."""

    mu: Any           # first moment
    nu: Any           # second moment
    count: Any        # i32 step counter


class TrainState(NamedTuple):
    """Everything owned by the learner, as one donated pytree.

    log_alpha/alpha_opt exist only for the SAC family (learned entropy
    temperature). They default to None — which JAX treats as an EMPTY
    pytree node — so every non-SAC TrainState keeps its exact historical
    leaf structure: checkpoints, sharding-spec trees, and tree.maps all
    compose unchanged."""

    actor_params: Any
    critic_params: Any
    target_actor_params: Any
    target_critic_params: Any
    actor_opt: OptState
    critic_opt: OptState
    step: Any         # i32
    log_alpha: Any = None   # f32 scalar (SAC only)
    alpha_opt: Any = None   # OptState over log_alpha (SAC autotune only)


def batch_from_numpy(arrays: Dict[str, np.ndarray]) -> Batch:
    return Batch(
        obs=arrays["obs"],
        action=arrays["action"],
        reward=arrays["reward"],
        discount=arrays["discount"],
        next_obs=arrays["next_obs"],
        weight=arrays.get("weight", np.ones_like(arrays["reward"])),
    )


# --- packed-batch wire format -----------------------------------------------
#
# Host->device transfers pay a per-array overhead on top of the payload
# (size on the chip: not measured), so minibatches cross the
# boundary as ONE [..., B, D] f32 array with fields concatenated on the last
# axis in this fixed order; `unpack_batch` slices them apart inside jit,
# where the slices fuse into the consumers for free.

def packed_width(obs_dim: int, act_dim: int) -> int:
    return 2 * obs_dim + act_dim + 3


def pack_batch_np(arrays: Dict[str, np.ndarray]) -> np.ndarray:
    """[..., B, field] dict -> [..., B, D] packed f32 array (host side)."""
    reward = np.asarray(arrays["reward"], np.float32)[..., None]
    discount = np.asarray(arrays["discount"], np.float32)[..., None]
    weight = arrays.get("weight")
    weight = (
        np.ones_like(reward)
        if weight is None
        else np.asarray(weight, np.float32)[..., None]
    )
    return np.concatenate(
        [arrays["obs"], arrays["action"], reward, discount, arrays["next_obs"], weight],
        axis=-1,
        dtype=np.float32,
    )


def unpack_batch(packed, obs_dim: int, act_dim: int) -> Batch:
    """Inverse of pack_batch_np; works on jnp arrays inside jit, where the
    slices read as the chunk programs' `cut` (trace.CHUNK_SCOPES)."""
    o = obs_dim
    a = act_dim
    with device_scope("cut"):
        return Batch(
            obs=packed[..., :o],
            action=packed[..., o : o + a],
            reward=packed[..., o + a],
            discount=packed[..., o + a + 1],
            next_obs=packed[..., o + a + 2 : 2 * o + a + 2],
            weight=packed[..., 2 * o + a + 2],
        )
