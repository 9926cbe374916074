"""DDPG/D4PG agent: ties networks, replay, noise, and the jitted learner step
together behind the reference's agent surface — `act(state)`,
`observe(transition)`, `train_step()` (SURVEY.md §1 'Agent / algorithm',
§2 #2 `ddpg.py`).

This class is the single-process composition (ladder rung 1,
BASELINE.json:7). The distributed composition reuses the same pieces:
actors/ run `act`+`observe` in worker processes, the train.py driver loop
runs `train_step` against the sharded mesh learner (parallel/learner.py).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.envs.registry import EnvSpec
from distributed_ddpg_tpu.learner import (
    StepOutput,
    init_train_state,
    jit_learner_step,
    make_act_fn,
    make_sample_fn,
)
from distributed_ddpg_tpu.ops import support_auto
from distributed_ddpg_tpu.ops.noise import OUNoise
from distributed_ddpg_tpu.replay import NStepAccumulator, make_replay
from distributed_ddpg_tpu.types import Batch, batch_from_numpy


class DDPGAgent:
    def __init__(self, config: DDPGConfig, spec: EnvSpec):
        self.config = config
        self.spec = spec
        self.state = init_train_state(config, spec.obs_dim, spec.act_dim, config.seed)
        self._step_fn = jit_learner_step(
            config, spec.action_scale, action_offset=spec.action_offset
        )
        self._act_fn = make_act_fn(
            config, spec.action_scale, action_offset=spec.action_offset
        )
        # SAC explores by sampling its own policy; OU noise stays unused.
        self._sample_fn = (
            make_sample_fn(config, spec.action_scale, action_offset=spec.action_offset)
            if config.gaussian_head
            else None
        )
        self._act_key = jax.random.PRNGKey(config.seed + 2) if config.gaussian_head else None
        # Uniform-random warmup (SAC start_steps; config.warmup_uniform_steps).
        self._warmup_uniform = config.resolved_warmup_uniform()
        self._warmup_rng = np.random.default_rng(config.seed + 3)
        self._env_steps = 0
        self.replay = make_replay(config, spec.obs_dim, spec.act_dim)
        self.noise = OUNoise(
            (spec.act_dim,),
            theta=config.ou_theta,
            sigma=config.ou_sigma,
            dt=config.ou_dt,
            seed=config.seed + 1,
        )
        self.nstep = NStepAccumulator(config.n_step, config.gamma)
        self._learn_steps = 0
        # Auto C51 support (resolved lazily at the first train_step; the
        # flag must outlive the resolution — after it self.config carries
        # concrete bounds and v_support_auto reads False).
        self._support_auto_active = config.distributional and config.v_support_auto
        self._support_controller = support_auto.SupportController()

    def _set_value_bounds(self, v_min: float, v_max: float) -> None:
        self.config = self.config.replace(v_min=float(v_min), v_max=float(v_max))
        self._step_fn = jit_learner_step(
            self.config, self.spec.action_scale,
            action_offset=self.spec.action_offset,
        )

    # --- acting (SURVEY.md §3.2) ---

    def act(self, obs: np.ndarray, explore: bool = True) -> np.ndarray:
        if explore and self._env_steps < self._warmup_uniform:
            return self._warmup_rng.uniform(
                self.spec.action_low, self.spec.action_high
            ).astype(np.float32)
        if explore and self.config.gaussian_head:
            self._act_key, k = jax.random.split(self._act_key)
            action = np.asarray(
                self._sample_fn(self.state.actor_params, obs[None], k)
            )[0]
            return np.clip(action, self.spec.action_low, self.spec.action_high)
        action = np.asarray(self._act_fn(self.state.actor_params, obs[None]))[0]
        if explore:
            action = action + self.noise() * self.spec.action_scale
        return np.clip(action, self.spec.action_low, self.spec.action_high)

    def reset_episode(self) -> None:
        self.noise.reset()
        self.nstep.reset()

    # --- experience (SURVEY.md §3.2 replay.add) ---

    def observe(self, obs, action, reward, done, next_obs) -> None:
        self._env_steps += 1
        for o, a, r, disc, nobs in self.nstep.push(
            obs[None], action[None], [reward], [done], next_obs[None]
        ):
            self.replay.add(o, a, r, disc, nobs)

    # --- learning (SURVEY.md §3.3) ---

    def can_train(self) -> bool:
        return len(self.replay) >= max(self.config.replay_min_size, self.config.batch_size)

    def train_step(self) -> Optional[Dict[str, float]]:
        if not self.can_train():
            return None
        if self.config.distributional and self.config.v_support_auto:
            # Auto C51 support (ops/support_auto.py): the replay just crossed
            # the warmup threshold, so size the bounds from its reward
            # statistics and rebuild the (lazily jitted) step — no compile
            # has happened yet, so this costs nothing extra. After this the
            # config carries concrete bounds and the branch never re-enters.
            # Running expansion: the SupportController check further down.
            v_lo, v_hi = support_auto.replay_data_bounds(
                self.replay, self.config.gamma, self.config.n_step
            )
            self._set_value_bounds(v_lo, v_hi)
        sample = self.replay.sample(self.config.batch_size)
        indices = sample.pop("indices")
        batch = batch_from_numpy(sample)
        out: StepOutput = self._step_fn(self.state, batch)
        self.state = out.state
        self._learn_steps += 1
        support_metrics = {}
        if self._support_auto_active and self._learn_steps % 50 == 0:
            # Corroborated against the replay's CURRENT rewards — a
            # diverging mean_q must not drag the support up
            # (support_auto docstring, seed-1 incident).
            grown = self._support_controller.check(
                self.config.v_min, self.config.v_max,
                float(out.metrics["mean_q"]), self._learn_steps,
                data_bounds_fn=lambda: support_auto.replay_data_bounds(
                    self.replay, self.config.gamma, self.config.n_step
                ),
            )
            if grown is not None:
                self._set_value_bounds(*grown)
        if self._support_auto_active:
            # Same observability as the train_jax path: the refusal count
            # is the diverging-critic signature.
            support_metrics = dict(
                support_refusals=self._support_controller.refusals
            )
        if self.config.prioritized:
            # The only extra device->host transfer PER costs (uniform replay
            # skips it entirely — update_priorities would be a no-op).
            self.replay.update_priorities(indices, np.asarray(out.td_errors))
            frac = min(1.0, self._learn_steps / self._expected_learn_steps())
            self.replay.set_beta(
                self.config.per_beta
                + frac * (self.config.per_beta_final - self.config.per_beta)
            )
        return {
            **{k: float(v) for k, v in jax.device_get(out.metrics).items()},
            **support_metrics,
        }

    def _expected_learn_steps(self) -> int:
        """Learner steps this run will take — the PER beta annealing horizon
        (learner steps lag env steps by the warmup and by train_every)."""
        cfg = self.config
        return max(1, (cfg.total_env_steps - cfg.replay_min_size) // cfg.train_every)

    # --- evaluation ---

    def evaluate(self, env, episodes: int = 5, seed: int = 10_000) -> float:
        returns = []
        for ep in range(episodes):
            obs, _ = env.reset(seed=seed + ep)
            done = False
            total = 0.0
            while not done:
                action = self.act(obs, explore=False)
                obs, r, terminated, truncated, _ = env.step(action)
                total += r
                done = terminated or truncated
            returns.append(total)
        return float(np.mean(returns))
