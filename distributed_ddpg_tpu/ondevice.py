"""Fully on-device training: env physics, exploration, replay, and learner
in ONE compiled XLA program per chunk (`--backend=jax_ondevice`).

This is the TPU-native end state of SURVEY.md §7's 'hard part (a)' (feeding
a 20x-faster learner): for envs with JAX dynamics (envs/jax_envs.py) there
is nothing left to feed — E vectorized envs, the OU noise process, the
device-resident replay ring, and the fused learner step all live in the
same `lax.scan`, so a K-iteration chunk runs K*E env steps and K gradient
steps with ZERO host<->device transfers inside the chunk (only scalar
metrics come out). The reference's topology (SURVEY.md §1: N worker
processes + parameter server over gRPC) needs a process boundary because
TF-1.x envs and learners can't fuse; on TPU the boundary itself was the
bottleneck, so this backend removes it rather than reimplementing it.

Semantics per scan iteration:
  1. OU noise update (theta/sigma/dt from config) on device, per env;
  2. a = clip(mu(s) + scale * ou, bounds) for all E envs (one MXU matmul);
  3. vmapped env.step with auto-reset; the stored transition bootstraps on
     the PRE-reset observation (jax_envs.StepOut.boot_obs);
  4. scatter the E packed transitions into the replay ring (mod-capacity);
  5. one learner step on a uniform sample of `batch_size` rows (gated off
     until `replay_min_size` rows exist — lax.cond, so warmup needs no
     separate compiled program).

The E envs play the role of the reference's N async actors (config reuses
`num_actors` for E); the effective replay ratio is E env steps per gradient
step. Data-parallelism: the minibatch AND the env batch shard over the
mesh's 'data' axis (envs replicate if E doesn't divide it); params follow
parallel/mesh.state_pspec (replicated, or TP-sharded when model_axis > 1).

Termination contract: `jax_envs.StepOut.terminated` distinguishes TRUE
termination (absorbing state — bootstrap discount 0) from time-limit
truncation (done without terminated — bootstrapping continues), and the
scan body folds it into the stored discount column as
`gamma * (1 - terminated)`. JaxPendulum only truncates (discounts are
always gamma); JaxMountainCar truly terminates at the goal and exercises
the split end to end (tests/test_ondevice.py).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.envs.jax_envs import make_jax_env
from distributed_ddpg_tpu.learner import (
    init_train_state,
    make_learner_step,
    metric_keys,
)
from distributed_ddpg_tpu.ops.exploration import vector_env_step
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.types import TrainState, packed_width, unpack_batch


class Carry(NamedTuple):
    """Everything the on-device loop owns, as one donated pytree."""

    train: TrainState
    env_state: object        # vmapped env state pytree, leading dim E
    obs: jnp.ndarray         # f32[E, obs_dim] current policy observations
    ou: jnp.ndarray          # f32[E, act_dim] OU noise state
    ep_ret: jnp.ndarray      # f32[E] running episode returns
    storage: jnp.ndarray     # f32[capacity, D] packed replay ring
    ptr: jnp.ndarray         # i32[]
    size: jnp.ndarray        # i32[]
    key: jnp.ndarray         # PRNG key


class ChunkStats(NamedTuple):
    metrics: dict            # mean learner metrics over the chunk (f32[])
    learn_steps: jnp.ndarray # i32[] learner steps actually taken (post-warmup)
    dones: jnp.ndarray       # bool[K, E] episode boundaries
    ep_returns: jnp.ndarray  # f32[K, E] episode return where done, else 0


class OnDeviceDDPG:
    def __init__(
        self,
        config: DDPGConfig,
        mesh: Optional[Mesh] = None,
        chunk_size: int = 64,
    ):
        if config.prioritized:
            raise ValueError(
                "jax_ondevice backend supports uniform replay only (PER "
                "priorities are host state; use --backend=jax_tpu)"
            )
        if config.n_step != 1:
            raise ValueError(
                "jax_ondevice backend stores 1-step transitions (n-step "
                "windows are a host-accumulator feature; use --backend=jax_tpu)"
            )
        if config.train_every != 1:
            raise ValueError(
                "jax_ondevice backend runs one learner step per vector env "
                "step (train_every is a host-loop knob; use --backend=jax_tpu)"
            )
        if config.resolved_warmup_uniform() >= config.replay_capacity:
            raise ValueError(
                "warmup_uniform_steps must be < replay_capacity on "
                "jax_ondevice: the warmup gate reads the ring-fill counter, "
                "which saturates at capacity — a larger budget would act "
                "uniformly forever"
            )
        self.config = config
        keys = metric_keys(config)
        self.env = make_jax_env(config.env_id)
        self.num_envs = int(config.num_actors)
        self.chunk_size = int(chunk_size)
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh(
            config.data_axis, config.model_axis
        )
        data_size = self.mesh.shape["data"]
        # Same per-device batch semantics as the sharded learner
        # (parallel/learner.py global_batch): scale_batch_with_data draws
        # batch_size rows per data-axis device, so throughput grows with
        # the mesh instead of slicing a fixed batch thinner.
        self.global_batch = (
            config.batch_size * data_size
            if config.scale_batch_with_data
            else config.batch_size
        )
        if self.global_batch % data_size:
            raise ValueError(
                f"batch_size={config.batch_size} not divisible by data axis "
                f"size {data_size}"
            )

        env = self.env
        E = self.num_envs
        obs_dim, act_dim = env.obs_dim, env.act_dim
        self.obs_dim, self.act_dim = obs_dim, act_dim
        width = packed_width(obs_dim, act_dim)
        scale = ((env.action_high - env.action_low) / 2.0).astype(np.float32)
        offset = ((env.action_high + env.action_low) / 2.0).astype(np.float32)
        self.action_scale, self.action_offset = scale, offset
        low = jnp.asarray(env.action_low)
        high = jnp.asarray(env.action_high)

        step_fn = make_learner_step(config, scale, action_offset=offset)
        cfg = config
        capacity = cfg.replay_capacity
        min_fill = max(cfg.replay_min_size, cfg.batch_size)

        # Envs shard over 'data' when divisible; replicate otherwise (their
        # per-step FLOPs are negligible — sharding them is a bonus, not a need).
        env_axis = "data" if E % data_size == 0 else None
        env_spec = P(env_axis)

        warmup_uniform = cfg.resolved_warmup_uniform()

        def env_step(carry: Carry):
            # Shared exploration + step + packed-rows body
            # (ops/exploration.vector_env_step — one implementation for
            # this monolith AND the device-actor pool). Uniform warmup
            # (config.warmup_uniform_steps) gates on the RING FILL here —
            # valid because __init__ rejects warmup >= capacity (size
            # saturates there); worker.py parity: auto resolves > 0 only
            # for SAC, but an explicit budget means the same thing on
            # every backend.
            key, ou, action, out, rows = vector_env_step(
                cfg, env, E, carry.train.actor_params, carry.env_state,
                carry.obs, carry.ou, carry.key, scale, offset, low, high,
                warmup_active=(
                    carry.size < warmup_uniform
                    if warmup_uniform > 0
                    else None
                ),
            )
            idx = (carry.ptr + jnp.arange(E, dtype=jnp.int32)) % capacity
            storage = carry.storage.at[idx].set(rows)
            ep_ret = carry.ep_ret + out.reward
            done_returns = jnp.where(out.done, ep_ret, 0.0)
            return (
                Carry(
                    train=carry.train,
                    env_state=out.state,
                    obs=out.obs,
                    ou=ou,
                    ep_ret=jnp.where(out.done, 0.0, ep_ret),
                    storage=storage,
                    ptr=(carry.ptr + E) % capacity,
                    size=jnp.minimum(carry.size + E, capacity),
                    key=key,
                ),
                out.done,
                done_returns,
            )

        zero_metrics = {k: jnp.zeros((), jnp.float32) for k in keys}

        global_batch = self.global_batch

        def learn_step(carry: Carry):
            key, k_sample = jax.random.split(carry.key)
            idx = jax.random.randint(
                k_sample, (global_batch,), 0, jnp.maximum(carry.size, 1)
            )
            packed = jax.lax.with_sharding_constraint(
                carry.storage[idx], NamedSharding(self.mesh, P("data", None))
            )
            out = step_fn(carry.train, unpack_batch(packed, obs_dim, act_dim))
            return carry._replace(train=out.state, key=key), out.metrics

        def maybe_learn(carry: Carry):
            return jax.lax.cond(
                carry.size >= min_fill,
                lambda c: learn_step(c) + (jnp.int32(1),),
                lambda c: (c, zero_metrics, jnp.int32(0)),
                carry,
            )

        def chunk(carry: Carry):
            def body(c, _):
                c, done, done_ret = env_step(c)
                c, metrics, learned = maybe_learn(c)
                return c, (metrics, learned, done, done_ret)

            carry, (ms, learned, dones, ep_returns) = jax.lax.scan(
                body, carry, None, length=self.chunk_size
            )
            n = jnp.sum(learned)
            # Mean over the iterations that actually learned (0-safe).
            metrics = jax.tree.map(
                lambda x: jnp.sum(x) / jnp.maximum(n, 1).astype(jnp.float32), ms
            )
            return carry, ChunkStats(
                metrics=metrics,
                learn_steps=n,
                dones=dones,
                ep_returns=ep_returns,
            )

        # --- shardings over the whole carry ---
        state = init_train_state(config, obs_dim, act_dim, config.seed)
        state_spec = mesh_lib.state_pspec(state, self.mesh)
        key = jax.random.PRNGKey(config.seed)
        k_init, k_run = jax.random.split(key)
        env_state = jax.vmap(env.init)(jax.random.split(k_init, E))
        carry = Carry(
            train=state,
            env_state=env_state,
            obs=jax.vmap(env.observe)(env_state),
            ou=jnp.zeros((E, act_dim), jnp.float32),
            ep_ret=jnp.zeros((E,), jnp.float32),
            storage=jnp.zeros((capacity, width), jnp.float32),
            ptr=jnp.zeros((), jnp.int32),
            size=jnp.zeros((), jnp.int32),
            key=k_run,
        )
        carry_spec = Carry(
            train=state_spec,
            env_state=jax.tree.map(lambda _: env_spec, env_state),
            obs=P(env_axis, None),
            ou=P(env_axis, None),
            ep_ret=P(env_axis),
            storage=P(None, None),
            ptr=P(),
            size=P(),
            key=P(),
        )
        self._carry_sharding = mesh_lib.to_named(self.mesh, carry_spec)
        stats_spec = ChunkStats(
            metrics={k: P() for k in keys},
            learn_steps=P(),
            dones=P(None, env_axis),
            ep_returns=P(None, env_axis),
        )
        self._chunk = jax.jit(
            chunk,
            in_shardings=(self._carry_sharding,),
            out_shardings=(
                self._carry_sharding,
                mesh_lib.to_named(self.mesh, stats_spec),
            ),
            donate_argnums=(0,),
        )
        # --- compile-once multi-chunk superstep (config.superstep_beats;
        # parallel/superstep.py is the jax_tpu sibling) --- B chunk bodies
        # inside one donated-carry fori_loop: the ChunkStats rows stack
        # into a device-side [B, ...] carry, and finalize_stats pays ONE
        # device_get for the whole superstep. ALL B chunks run inside the
        # loop body (stats zero-initialized from eval_shape at trace
        # time): the body compiles as its own isolated computation with
        # the same codegen as the standalone chunk program — inlining the
        # first chunk instead lets XLA cross-optimize it with the loop
        # and diverge at ULP level (parallel/superstep.py, same finding).
        # Scope: exact parity is a SINGLE-device property; on a
        # multi-device mesh XLA schedules the collectives differently in
        # the loop body than in the standalone program, so SPMD runs
        # agree only to float32 tolerance (tests/test_superstep.py).
        self.superstep_beats = int(config.superstep_beats)
        self._superstep = None
        if self.superstep_beats > 1:
            B = self.superstep_beats

            def superstep(carry: Carry):
                stats_shapes = jax.eval_shape(chunk, carry)[1]
                stacked = jax.tree.map(
                    lambda s: jnp.zeros((B,) + s.shape, s.dtype),
                    stats_shapes,
                )

                def body(i, acc):
                    carry, stacked = acc
                    carry, s = chunk(carry)
                    stacked = jax.tree.map(
                        lambda a, x: a.at[i].set(x), stacked, s
                    )
                    return carry, stacked

                return jax.lax.fori_loop(0, B, body, (carry, stacked))

            stacked_spec = ChunkStats(
                metrics={k: P(None) for k in keys},
                learn_steps=P(None),
                dones=P(None, None, env_axis),
                ep_returns=P(None, None, env_axis),
            )
            self._superstep = jax.jit(
                superstep,
                in_shardings=(self._carry_sharding,),
                out_shardings=(
                    self._carry_sharding,
                    mesh_lib.to_named(self.mesh, stacked_spec),
                ),
                donate_argnums=(0,),
            )
        self.carry: Carry = jax.device_put(carry, self._carry_sharding)
        self._env_steps = 0
        self._learn_steps = 0

    # --- driving ---

    def run_chunk(self) -> ChunkStats:
        """K scan iterations = K*E env steps + up-to-K learner steps."""
        self.carry, stats = self._chunk(self.carry)
        self._env_steps += self.chunk_size * self.num_envs
        return stats

    def run_superstep(self) -> ChunkStats:
        """B chunks as ONE fori_loop dispatch (superstep_beats > 1):
        B*K*E env steps + up-to-B*K learner steps, stats stacked [B, ...]
        on device — finalize_stats flattens them in the same single
        device_get a lone chunk pays."""
        self.carry, stats = self._superstep(self.carry)
        self._env_steps += (
            self.superstep_beats * self.chunk_size * self.num_envs
        )
        return stats

    def finalize_stats(self, stats: ChunkStats) -> dict:
        """Device stats -> host floats (one sync point per dispatch).
        Accepts a single chunk's stats OR a superstep's stacked [B, ...]
        rows (detected by learn_steps rank): stacked rows flatten so the
        episode accounting is identical to B sequential chunks, and the
        metric means re-weight by each chunk's learned-iteration count
        (each row is already a per-chunk mean; an unweighted mean would
        skew toward warmup chunks that learned less)."""
        host = jax.device_get(stats)
        ls = np.asarray(host.learn_steps)
        dones = np.asarray(host.dones)
        rets = np.asarray(host.ep_returns)
        if ls.ndim == 0:
            self._learn_steps += int(ls)
            out = {k: float(v) for k, v in host.metrics.items()}
        else:
            self._learn_steps += int(ls.sum())
            dones = dones.reshape((-1,) + dones.shape[2:])
            rets = rets.reshape((-1,) + rets.shape[2:])
            w = ls.astype(np.float64) / max(float(ls.sum()), 1.0)
            out = {
                k: float((np.asarray(v, np.float64) * w).sum())
                for k, v in host.metrics.items()
            }
        rets = rets[dones]
        out["episodes"] = int(dones.sum())
        if rets.size:
            out["episode_return"] = float(rets.mean())
        return out

    @property
    def env_steps(self) -> int:
        return self._env_steps

    @property
    def learn_steps(self) -> int:
        return self._learn_steps

    # --- host-side views (checkpoint / eval) ---

    @property
    def state(self) -> TrainState:
        return self.carry.train

    def actor_params_to_host(self):
        return jax.tree.map(np.asarray, jax.device_get(self.carry.train.actor_params))

    def load_train_state(self, state: TrainState) -> None:
        state = jax.device_put(state, self._carry_sharding.train)
        self.carry = self.carry._replace(train=state)

    def replay_state_dict(self) -> dict:
        n = int(jax.device_get(self.carry.size))
        storage = np.asarray(jax.device_get(self.carry.storage))
        return {
            "packed": storage[:n].copy(),
            "ptr": np.asarray(int(jax.device_get(self.carry.ptr))),
            "size": np.asarray(n),
        }

    def load_replay_state(self, state: dict) -> None:
        n = int(state["size"])
        storage = np.array(jax.device_get(self.carry.storage))
        storage[:n] = state["packed"]
        self.carry = self.carry._replace(
            storage=jax.device_put(
                jnp.asarray(storage), self._carry_sharding.storage
            ),
            ptr=jax.device_put(
                jnp.asarray(int(state["ptr"]) % self.config.replay_capacity, jnp.int32),
                self._carry_sharding.ptr,
            ),
            size=jax.device_put(
                jnp.asarray(n, jnp.int32), self._carry_sharding.size
            ),
        )


# ---------------------------------------------------------------------------
# program-contract analyzer hook (analysis/programs.py; docs/ANALYSIS.md
# "Layer 2")
# ---------------------------------------------------------------------------


def program_specs():
    """The fused env+replay+learner megastep as one traced program. The
    whole carry — train state, env states, the HBM ring — is donated; any
    leaf that stops aliasing doubles the RING in HBM, which is the
    costliest donation miss in the repo."""
    from distributed_ddpg_tpu.analysis.programs import (
        BuiltProgram,
        ProgramSpec,
        probe_config,
        probe_mesh,
    )

    def build():
        config = probe_config(num_actors=4, warmup_uniform_steps=8)
        od = OnDeviceDDPG(config, mesh=probe_mesh(), chunk_size=2)
        return BuiltProgram(od._chunk, (od.carry,), (0,))

    def build_superstep():
        # B=2: the smallest loop that actually iterates. The fori_loop's
        # donated carry includes the ring — aliasing must survive the
        # loop composition or the superstep doubles the RING in HBM.
        config = probe_config(
            num_actors=4, warmup_uniform_steps=8, superstep_beats=2
        )
        od = OnDeviceDDPG(config, mesh=probe_mesh(), chunk_size=2)
        return BuiltProgram(od._superstep, (od.carry,), (0,))

    return [
        ProgramSpec("ondevice.chunk", "ondevice.py", build),
        ProgramSpec("ondevice.superstep", "ondevice.py", build_superstep),
    ]
