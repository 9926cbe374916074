"""--strict_sync lockstep actor pool (SURVEY.md §5 'Race detection' row;
VERDICT r4 Missing #5).

The production ActorPool runs workers in separate processes: experience
arrival order, param-refresh timing, and drain interleaving all depend on
OS scheduling, so two runs of the same config differ bit-for-bit — which is
exactly what makes an async race impossible to replay. SyncActorPool is the
debug-mode replacement: the SAME worker semantics (NumpyPolicy + OU noise /
uniform warmup / n-step accumulation / truncation flush, mirroring
actors/worker.py run_worker step for step) executed INLINE on the driver
thread in a fixed round-robin env order. Every drain steps the envs a
deterministic number of times (the caller's ingest budget), so the whole
ingest→learn schedule is a pure function of the config — two runs produce
bit-identical metrics (tests/test_strict_sync.py) and any divergence from
an async run isolates the race to the async machinery.

One env step per grad step: train_jax requires both ratio gates armed with
strict_sync (config.py validation), which pins learner and ingest to the
configured ratio deterministically — at the default 1.0/1.0 that is the
reference's synchronous 1:1 schedule.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from distributed_ddpg_tpu.actors.policy import (
    NumpyPolicy,
    flatten_params,
    layout_of,
)
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.envs import make
from distributed_ddpg_tpu.envs.registry import EnvSpec
from distributed_ddpg_tpu.metrics import nstep_counters
from distributed_ddpg_tpu.ops.noise import OUNoise
from distributed_ddpg_tpu.replay.nstep import NStepAccumulator


class _InlineActor:
    """One env's worth of worker state — the per-process state of
    actors/worker.py run_worker, held inline."""

    def __init__(self, config: DDPGConfig, spec: EnvSpec, seed: int):
        self.spec = spec
        self.env = make(config.env_id, seed=seed)
        self.noise = OUNoise(
            (spec.act_dim,),
            theta=config.ou_theta,
            sigma=0.0 if config.gaussian_head else config.ou_sigma,
            dt=config.ou_dt,
            seed=seed,
        )
        self.nstep = NStepAccumulator(config.n_step, config.gamma)
        self.warmup_rng = np.random.default_rng(seed + 7919)
        self.obs, _ = self.env.reset(seed=seed)
        self.ep_return = 0.0
        self.ep_len = 0

    def step(self, policy: NumpyPolicy, uniform: bool) -> tuple:
        """One env step; returns (nstep_rows, finished_episode|None)."""
        spec = self.spec
        if uniform:
            action = self.warmup_rng.uniform(
                spec.action_low, spec.action_high
            ).astype(np.float32)
        else:
            action = policy(self.obs)[0] + self.noise() * np.asarray(
                spec.action_scale, np.float32
            )
        action = np.clip(action, spec.action_low, spec.action_high).astype(
            np.float32
        )
        next_obs, reward, terminated, truncated, _ = self.env.step(action)
        rows = list(
            self.nstep.push(
                self.obs[None], action[None], [reward], [terminated],
                next_obs[None],
            )
        )
        self.ep_return += reward
        self.ep_len += 1
        self.obs = next_obs
        episode = None
        if terminated or truncated:
            if truncated and not terminated:
                from distributed_ddpg_tpu.actors.worker import _flush_truncated

                rows.extend(_flush_truncated(self.nstep, next_obs))
            episode = (self.ep_return, self.ep_len)
            self.obs, _ = self.env.reset()
            self.noise.reset()
            self.nstep.reset()
            self.ep_return, self.ep_len = 0.0, 0
        return rows, episode


class SyncActorPool:
    """Drop-in ActorPool replacement with deterministic inline stepping.
    Same driver-facing surface (train.py uses: start/stop/broadcast/
    drain_batches/drain_into/steps_received/monitor/episode_stats/
    staleness/nstep_counters/policy_forward/env_steps_offset)."""

    def __init__(self, config: DDPGConfig, spec: EnvSpec,
                 num_actors: Optional[int] = None):
        self.config = config
        self.spec = spec
        self.num_actors = num_actors or config.num_actors
        self.layout = layout_of(config, spec.obs_dim, spec.act_dim)
        self._policy = NumpyPolicy(
            self.layout,
            spec.action_scale,
            spec.action_offset,
            gaussian=config.gaussian_head,
            stochastic=config.gaussian_head,
            seed=config.seed + 1,
            log_std_min=config.sac_log_std_min,
            log_std_max=config.sac_log_std_max,
            squash=not config.mpo,
        )
        self._actors: List[_InlineActor] = []
        self._episodes: List[tuple] = []
        self._steps_received = 0
        self._env_steps_taken = 0
        self._next = 0  # round-robin cursor
        self._broadcast_step = 0
        self.env_steps_offset = 0

    # --- lifecycle ---

    def start(self, actor_params) -> "SyncActorPool":
        self._policy.load_flat(flatten_params(actor_params))
        self._actors = [
            # Same per-worker seed spacing as ActorPool._spawn gives its
            # processes a distinct stream per actor.
            _InlineActor(self.config, self.spec, self.config.seed + 101 * i)
            for i in range(self.num_actors)
        ]
        return self

    def stop(self) -> None:
        for a in self._actors:
            close = getattr(a.env, "close", None)
            if close is not None:
                close()
        self._actors = []

    # --- params ---

    def broadcast(self, actor_params, learner_step: int = 0) -> None:
        self._policy.load_flat(flatten_params(actor_params))
        self._broadcast_step = learner_step

    def staleness(self) -> Dict[str, float]:
        # Lockstep: experience is produced synchronously under the latest
        # broadcast params — staleness is zero by construction.
        return {"staleness_mean": 0.0, "staleness_max": 0}

    def policy_forward(self) -> Dict[str, float]:
        """ActorPool.policy_forward: the inline actors time nothing."""
        return {}

    def nstep_counters(self) -> Dict[str, int]:
        """ActorPool.nstep_counters, over the inline actors."""
        if self.config.n_step <= 1:
            return {}
        return nstep_counters(
            [n for a in self._actors for n in (a.nstep.rows, a.nstep.short_rows)]
        )

    # --- experience ---

    def _produce(self, n_steps: int) -> List[Dict[str, np.ndarray]]:
        """Step the envs round-robin exactly n_steps times; returns the
        resulting n-step rows as one batch dict (possibly empty while the
        accumulators warm)."""
        warmup_total = self.config.resolved_warmup_uniform()
        fields: Dict[str, List[np.ndarray]] = {
            "obs": [], "action": [], "reward": [], "discount": [],
            "next_obs": [],
        }
        produced = 0
        for _ in range(n_steps):
            idx = self._next
            actor = self._actors[idx]
            self._next = (idx + 1) % self.num_actors
            uniform = (
                self.env_steps_offset + self._env_steps_taken < warmup_total
            )
            rows, episode = actor.step(self._policy, uniform)
            self._env_steps_taken += 1
            if episode is not None:
                # Same tuple shape as ActorPool's episode queue:
                # (actor_id, episode_return, episode_length).
                self._episodes.append((idx,) + episode)
            # nstep.push yields UNBATCHED rows: (obs_dim,), (act_dim,),
            # scalar reward/discount, (obs_dim,).
            for o, a, r, disc, nobs in rows:
                fields["obs"].append(o)
                fields["action"].append(a)
                fields["reward"].append(np.float32(r))
                fields["discount"].append(np.float32(disc))
                fields["next_obs"].append(nobs)
                produced += 1
        if not produced:
            return []
        batch = {
            "obs": np.stack(fields["obs"]),
            "action": np.stack(fields["action"]),
            "reward": np.asarray(fields["reward"], np.float32),
            "discount": np.asarray(fields["discount"], np.float32),
            "next_obs": np.stack(fields["next_obs"]),
        }
        self._steps_received += produced
        return [batch]

    def drain_batches(
        self, max_batches: int = 1000, max_rows: Optional[int] = None,
        with_sources: bool = False,
    ) -> List:
        if max_rows is None or max_rows <= 0:
            # strict_sync requires the ingest gate armed (config.py), so a
            # budget always arrives on the hot path; the warmup loop's
            # budget is the min-fill allowance.
            return []
        batches = self._produce(int(max_rows))
        if with_sources:
            # Inline actors interleave round-robin into ONE batch; there
            # is no per-row source to attribute (and no process to
            # quarantine) — the guardrails treat -1 as "untracked".
            return [(-1, b) for b in batches]
        return batches

    def drain_into(self, replay, max_batches: int = 1000,
                   max_rows: Optional[int] = None) -> int:
        moved = 0
        for batch in self.drain_batches(max_batches, max_rows):
            replay.add_batch(
                batch["obs"], batch["action"], batch["reward"],
                batch["discount"], batch["next_obs"],
            )
            moved += len(batch["reward"])
        return moved

    # --- bookkeeping ---

    @property
    def steps_received(self) -> int:
        # ROWS delivered, matching ActorPool's accounting exactly: the
        # driver's ingest budget and total_env_steps both count received
        # rows, and the warmup fill loop must see the gate open until the
        # REPLAY (not the env clock) reaches min_fill — the n-step
        # accumulator's held-back rows would otherwise stall warmup at the
        # budget boundary. The true env clock (self._env_steps_taken) runs
        # slightly ahead and only gates the uniform-warmup budget.
        return self._steps_received

    def episode_stats(self) -> List[tuple]:
        out, self._episodes = self._episodes, []
        return out

    def monitor(self) -> Dict[str, int]:
        return {"respawned": 0, "total_respawns": 0, "quarantined": 0}

    def recovery_counters(self) -> Dict[str, int]:
        # Inline actors cannot crash independently of the driver; the
        # counters exist for JSONL-schema parity with ActorPool.
        return {"actor_respawns": 0, "actor_quarantined": 0}

    def quarantine_source(self, worker_id: int, why: str = "numeric") -> bool:
        # Inline actors share the driver process; there is nothing to
        # quarantine (surface parity with ActorPool for the guardrails).
        return False
