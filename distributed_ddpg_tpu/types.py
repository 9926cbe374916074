"""Core pytree types: transitions, batches, and the learner TrainState.

The reference keeps its state scattered across TF graph variables on the
parameter server (SURVEY.md §1 'Distribution/comm'); here everything the
learner owns is ONE explicit pytree so the whole train step — losses, Adam,
Polyak — jits into a single XLA program with no host round trips
(SURVEY.md §3.3/§3.4).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple, Union

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


class Windows(NamedTuple):
    """A replay minibatch of WINDOWS (a recurrent configuration's rows,
    `unpack_windows`): L steps of one episode a row, left-aligned, a time
    axis behind the batch's. o_{t+1} is `obs`' next slot; replay is uniform,
    so there is no weight."""

    obs: Any          # f32[B, L + 1, obs_dim]   o_0 .. o_L
    action: Any       # f32[B, L, act_dim]
    reward: Any       # f32[B, L]
    terminated: Any   # f32[B, L]  1 where the step truly terminated (the learner multiplies gamma in)
    mask: Any         # f32[B, L]  1 on a real step, 0 on a padded one


class OptState(NamedTuple):
    """Adam state for one parameter tree (matches optax.adam semantics)."""

    mu: Any           # first moment
    nu: Any           # second moment
    count: Any        # i32 step counter


class TrainState(NamedTuple):
    """Everything owned by the learner, as one donated pytree.

    log_alpha/alpha_opt hold the learned scalars beside the nets, for the
    two families that have any. SAC: the entropy temperature, log_alpha one
    f32 scalar and alpha_opt its Adam (under sac_autotune). MPO
    (config.mpo): the four dual variables as a small tree, log_alpha a dict
    of `log_temperature` and `log_penalty_temperature` (f32[1]) and
    `log_alpha_mean` and `log_alpha_stddev` (f32[act_dim]), and alpha_opt
    the OptState of an Adam at its own rate (config.dual_lr) over that tree.
    They default to None — which JAX treats as an EMPTY pytree node — so
    every other TrainState keeps its exact historical leaf structure:
    checkpoints, sharding-spec trees, and tree.maps all compose unchanged."""

    actor_params: Any
    critic_params: Any
    target_actor_params: Any
    target_critic_params: Any
    actor_opt: OptState
    critic_opt: OptState
    step: Any         # i32
    log_alpha: Any = None   # SAC: f32 scalar; MPO: the dual variables' tree
    alpha_opt: Any = None   # OptState over log_alpha (SAC autotune, MPO)


class ObsSpec(NamedTuple):
    """An observation's shape and dtype: the one place that knows how many
    float32 words of a ring row an observation takes and whether the nets
    read it as a vector or as byte images. A flat observation of d floats is
    `ObsSpec((d,))`: d words, and everything derived from it is what the
    integer `obs_dim` gave. A byte observation (`uint8[C, H, W]`, the pixel
    configuration's) rides the float32 ring four pixels to a word, C * H * W
    / 4 words, as bits that nothing computes on (ops/pixels.py)."""

    shape: Tuple[int, ...]
    dtype: str = "float32"
    # Steps a ring row holds. 0: a row is one folded transition (o, a, R, d,
    # o', w). L > 0 (a recurrent configuration's config.seq_len): a row is a
    # WINDOW of L steps of one episode, L + 1 observations, L actions and 3 L
    # scalars (`packed_width`, `unpack_windows`).
    steps: int = 0

    @classmethod
    def of(cls, obs: Union[int, "ObsSpec"]) -> "ObsSpec":
        """`obs` itself, or the flat spec an integer `obs_dim` stands for."""
        if isinstance(obs, ObsSpec):
            return obs
        return cls((int(obs),))

    @classmethod
    def of_env(cls, env, steps: int = 0) -> "ObsSpec":
        """The observation of an environment or of its spec (anything with
        `obs_dim`, and `obs_shape` and `obs_dtype` where the observation is no
        flat float vector: envs/jax_envs.py, envs/registry.EnvSpec), in rows
        of `steps` steps (config.window_steps)."""
        shape = tuple(getattr(env, "obs_shape", ()) or ())
        spec = cls(shape, env.obs_dtype) if shape else cls.of(env.obs_dim)
        return spec._replace(steps=int(steps))

    @property
    def size(self) -> int:
        """Elements of one observation."""
        return int(np.prod(self.shape))

    @property
    def pixels(self) -> bool:
        return self.dtype == "uint8"

    @property
    def words(self) -> int:
        """float32 words of a ring row that hold one observation."""
        if not self.pixels:
            return self.size
        if self.size % 4:
            raise ValueError(
                f"a byte observation of shape {self.shape} does not fill "
                "whole 32-bit words of the ring's row"
            )
        return self.size // 4


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

def packed_width(obs_dim: Union[int, ObsSpec], act_dim: int) -> int:
    """float32 words of one packed row; `obs_dim` an observation's float
    count or its ObsSpec (a byte observation counts its words; a window of
    L steps holds L + 1 observations, L actions and 3 L scalars)."""
    obs = ObsSpec.of(obs_dim)
    if obs.steps:
        return (obs.steps + 1) * obs.words + obs.steps * (act_dim + 3)
    return 2 * obs.words + act_dim + 3


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


def unpack_windows(packed, obs_dim: int, act_dim: int, steps: int) -> Windows:
    """[..., D] rows of WINDOWS -> Windows. A window row is [o_0 .. o_L | a_0
    .. a_{L-1} | r_0 .. r_{L-1} | d_0 .. d_{L-1} | m_0 .. m_{L-1}], each field
    time-major: L = `steps` steps of one episode, left-aligned, d the steps'
    terminated flags and m 1 on a real step (ops/exploration.seq_fold writes
    it)."""
    o, a, n = obs_dim, act_dim, steps
    lead = packed.shape[:-1]
    at_a, at_r = (n + 1) * o, (n + 1) * o + n * a
    with device_scope("cut"):
        return Windows(
            obs=packed[..., :at_a].reshape(*lead, n + 1, o),
            action=packed[..., at_a:at_r].reshape(*lead, n, a),
            reward=packed[..., at_r : at_r + n],
            terminated=packed[..., at_r + n : at_r + 2 * n],
            mask=packed[..., at_r + 2 * n : at_r + 3 * n],
        )
