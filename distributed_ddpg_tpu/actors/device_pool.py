"""On-device vectorized actors: Podracer/Anakin-style rollouts that never
leave HBM (config.actor_backend='device'; docs/DEVICE_ACTORS.md; PAPERS.md
arXiv 2104.06272, with the device-resident sample path motivated by the
in-network experience-sampling line, arXiv 2110.13506).

The host pool (actors/pool.py) steps CPU envs in worker processes, runs OU
noise in numpy, and ships rows host->HBM through the ingest pipeline —
mandatory for Gym/Mujoco, but for envs with JAX dynamics
(envs/jax_envs.py) it caps rollout throughput at the host ingest path
(~300 rows/ms measured ceiling) while the accelerator learner is hundreds
of times faster than the CPU baseline. This pool removes the host from the
experience path entirely:

  - ONE jitted program per chunk: a `lax.scan` of K iterations, each
    advancing E vmapped envs — per-env OU noise update, a = clip(mu(s) +
    ou * scale, bounds) (one MXU matmul over the E-batch), vmapped
    env.step with auto-reset, and the packed [E, D] transition rows —
    returning a [K*E, D] block that is already device-resident;
  - the block scatters into DeviceReplay's HBM ring via
    `DeviceReplay.insert_device_rows` (a donated jitted insert): no host
    staging ring, no transfer-scheduler ingest class, zero host<->device
    bytes per transition. The scheduler keeps its other lanes (lockstep /
    prefetch / d2h / serve) untouched;
  - param refresh is a POINTER SWAP: `set_params` stores a reference to
    the learner's live (device-resident, correctly sharded) actor params,
    and the next rollout dispatch reads them — no pool-broadcast
    shared-memory copy, no d2h. train.py re-swaps every chunk (the
    previous chunk's dispatch DONATED the old TrainState, so the stale
    reference must never be dispatched again).

The learner keeps its full feature set — PER, guardrails, serving,
multi-host — because replay stays an ordinary DeviceReplay and the learner
programs are unchanged; this module only replaces WHO produces the rows.
The host pool can run alongside (num_actors > 0): both sources feed the
same ring, host rows through the ingest pipeline, device rows through the
donated insert, with the replay's host pointer-mirror advanced for both so
source attribution (guardrails) stays aligned.

Multi-host: the rollout and the insert are global SPMD programs over the
learner's (possibly process-spanning) mesh — every process executes the
identical program at the identical loop point (train_jax drives the pool
at lockstep sites only), so the rows landed in the replicated storage are
bit-identical on every replica and the `sync_ship` lockstep accounting for
HOST rows is untouched. Env state shards over the mesh's 'data' axis when
E divides it (physics FLOPs are negligible — sharding is a bonus); the
rows output is replicated, which is exactly what the replicated-storage
insert needs.

Failure contract (docs/RESILIENCE.md discipline): the `devactor:rollout`
chaos site ticks once per dispatch; a dispatch-time failure that left the
carry intact restarts bounded (<= 3, counter devactor_restarts, trace
instant devactor_restart) — past the budget, or when the donated carry was
already consumed, a typed DeviceActorError surfaces to the trainer.
"""

from __future__ import annotations

import sys
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.envs.jax_envs import make_jax_env
from distributed_ddpg_tpu.metrics import DevActorStats
from distributed_ddpg_tpu.types import ObsSpec
from distributed_ddpg_tpu.models import recurrent as recnet
from distributed_ddpg_tpu.ops.exploration import (
    nstep_fold,
    nstep_window,
    seq_fold,
    seq_window,
    sigma_ladder,
    vector_env_step,
)


class DeviceActorError(RuntimeError):
    """The device-actor rollout loop died past its bounded-restart budget
    (or with its donated carry already consumed); the original exception
    rides along as __cause__ — the same surfacing discipline as
    IngestError / PrefetchTimeout."""


def resolve_device_actor_chunk(config: DDPGConfig) -> int:
    """K (env steps per rollout dispatch): config.device_actor_chunk when
    set, else 64 on kernel-native TPU backends and 8 elsewhere — the same
    resolution discipline as resolve_learner_chunk, so CPU dev/test
    dispatches stay snappy while TPU chunks amortize dispatch overhead."""
    if config.device_actor_chunk > 0:
        return config.device_actor_chunk
    from distributed_ddpg_tpu.ops.fused_chunk import runs_native

    return 64 if runs_native() else 8


class ActorCarry(NamedTuple):
    """Everything the rollout loop owns between dispatches, as one donated
    pytree. Cumulative episode stats live ON DEVICE so the host only pays
    a two-scalar d2h at log cadence (snapshot), never per chunk."""

    env_state: object        # vmapped env state pytree, leading dim E
    obs: jnp.ndarray         # f32[E, obs_dim] current policy observations
    ou: jnp.ndarray          # f32[E, act_dim] OU noise state
    ep_ret: jnp.ndarray      # f32[E] running episode returns
    steps: jnp.ndarray       # i32[] cumulative env steps (warmup gate)
    episodes: jnp.ndarray    # i32[] cumulative finished episodes
    ret_sum: jnp.ndarray     # f32[] cumulative sum of finished returns
    key: jnp.ndarray         # PRNG key
    # n_step > 1 only (None otherwise: no leaf, and the 1-step program is
    # the one it always was): the rows each env has begun and not yet
    # emitted (ops/exploration.NStepWindow), and how many emitted rows held
    # fewer than n steps.
    window: object = None
    short_rows: object = None  # i32[] cumulative
    # A recurrent configuration only (None otherwise, as above): each env's
    # policy state between steps (models/recurrent.Memory: the cell's (h, c)
    # and the previous action and reward, all zeroed where an episode ends),
    # the last seq_len steps of its current episode (ops/exploration.
    # SeqWindow: the row it writes every step), and how many episodes' ends
    # have zeroed a memory.
    memory: object = None
    seq: object = None
    state_resets: object = None  # i32[] cumulative


class DeviceActorPool:
    """E vectorized JAX envs + policy + OU noise as one compiled rollout
    chunk, feeding DeviceReplay without leaving HBM (module docstring)."""

    def __init__(
        self,
        config: DDPGConfig,
        mesh: Optional[Mesh] = None,
        fault=None,
        warmup_offset: int = 0,
    ):
        from distributed_ddpg_tpu.parallel import mesh as mesh_lib

        self.config = config
        self.env = make_jax_env(config.env_id)
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh(
            config.data_axis, config.model_axis
        )
        self.num_envs = E = int(config.device_actor_envs)
        self.chunk_size = K = resolve_device_actor_chunk(config)
        self.rows_per_chunk = K * E
        self._fault = fault
        self._stats = DevActorStats(seed=config.seed)
        self._params = None
        self._restarts = 0
        self._max_restarts = 3
        self._dispatches = 0
        self._steps = 0
        # n-step rows (docs/DEVICE_ACTORS.md): each env emits, at every
        # step, the row it began n - 1 steps earlier, so after the n - 1
        # priming steps (set_params) every dispatch yields K * E whole rows
        # and (n - 1) * E steps are always taken and not yet in the ring.
        self.n_step = n = int(config.n_step)
        self._primed = n == 1
        # Learner updates between the parameters a dispatch read and the
        # newest the learner held then (staleness): sum, count, max of the
        # interval.
        self._params_version = 0
        self._stale = [0, 0, 0]
        self._short_seen = 0
        self._rows_emitted = 0
        self._rows_seen = 0
        # Interval episode accounting: snapshot() differences the carry's
        # cumulative device counters against these host mirrors.
        self._eps_seen = 0
        self._ret_seen = 0.0

        env = self.env
        # The float32 words of a row that one observation takes: its float
        # count, or a byte frame stack's bytes over four (types.ObsSpec).
        # A recurrent configuration's row is a window of seq_len steps.
        self.obs = ObsSpec.of_env(env, config.window_steps)
        recurrent = bool(config.recurrent)
        obs_dim, act_dim = self.obs.words, env.act_dim
        self.obs_dim, self.act_dim = obs_dim, act_dim
        scale = ((env.action_high - env.action_low) / 2.0).astype(np.float32)
        offset = ((env.action_high + env.action_low) / 2.0).astype(np.float32)
        self.action_scale, self.action_offset = scale, offset
        low = jnp.asarray(env.action_low)
        high = jnp.asarray(env.action_high)
        cfg = config
        # REMAINING uniform-warmup budget (actors/pool.py
        # warmup_budget_per_worker parity): resumed progress counts against
        # the global budget, so a restored run never re-injects random
        # actions into a trained replay.
        warmup_uniform = max(
            0, cfg.resolved_warmup_uniform() - int(warmup_offset)
        )

        # Envs shard over 'data' when divisible; replicate otherwise
        # (physics FLOPs are negligible either way).
        data_size = self.mesh.shape["data"]
        env_axis = "data" if E % data_size == 0 else None

        def env_step(params, carry: ActorCarry):
            """One vectorized env step — the shared ops/exploration body
            (noise -> action -> vmapped step -> packed rows; key always
            splits 4 ways so the host-stepped parity reference in the
            tests can replay the exact stream) plus this pool's episode
            accounting. The warmup gate reads the pool's OWN cumulative
            step counter, not the ring's fill: the pool shares the ring
            with other sources, so it counts its own production."""
            mean_action, memory = None, carry.memory
            if recurrent:
                # One LSTM step of the learner's own tree, on the memory
                # the carry holds (models/recurrent.actor_step).
                with trace.device_scope("policy"):
                    mean_action, (h, c) = recnet.actor_step(
                        params, carry.obs, memory, scale, offset
                    )
            key, ou, action, out, rows = vector_env_step(
                cfg, env, E, params, carry.env_state, carry.obs, carry.ou,
                carry.key, scale, offset, low, high,
                warmup_active=(
                    carry.steps < warmup_uniform
                    if warmup_uniform > 0
                    else None
                ),
                mean_action=mean_action,
            )
            seq, state_resets = carry.seq, carry.state_resets
            if recurrent:
                # The row of this step is the env's window, not the 1-step
                # row; the memory takes the action the ring holds (noise and
                # warm-up's uniform draw included) and the reward, and all of
                # it is zero again where the episode ended.
                with trace.device_scope("fold"):
                    seq, rows = seq_fold(seq, action, out)
                keep = 1.0 - out.done.astype(jnp.float32)
                memory = recnet.Memory(
                    h=h * keep[:, None], c=c * keep[:, None],
                    prev_action=action * keep[:, None],
                    prev_reward=out.reward * keep,
                )
                state_resets = state_resets + out.done.sum().astype(jnp.int32)
            window, short_rows = carry.window, carry.short_rows
            if n > 1:
                with trace.device_scope("fold"):
                    window, rows, short = nstep_fold(
                        window, rows, out, cfg.gamma, obs_dim, act_dim
                    )
                    short_rows = short_rows + short.sum().astype(jnp.int32)
            ep_ret = carry.ep_ret + out.reward
            done_ret = jnp.where(out.done, ep_ret, 0.0)
            new_carry = ActorCarry(
                env_state=out.state,
                obs=out.obs,
                ou=ou,
                ep_ret=jnp.where(out.done, 0.0, ep_ret),
                steps=carry.steps + E,
                episodes=carry.episodes + out.done.sum().astype(jnp.int32),
                ret_sum=carry.ret_sum + done_ret.sum(),
                key=key,
                window=window,
                short_rows=short_rows,
                memory=memory,
                seq=seq,
                state_resets=state_resets,
            )
            return new_carry, rows

        # Its name is the program's in a device trace (`jit_devactor_rollout`
        # on the XLA Modules line): the benchmark's actors readers find it.
        def devactor_rollout(params, carry: ActorCarry):
            with trace.device_scope("rollout"):
                carry, rows = jax.lax.scan(
                    lambda c, _: env_step(params, c), carry, None, length=K
                )
            # [K, E, D] -> [K*E, D], step-major: row order matches K serial
            # E-wide inserts, so the ring layout is what a per-step insert
            # sequence would have produced.
            return carry, rows.reshape(K * E, rows.shape[-1])

        def prime(params, carry: ActorCarry):
            """The run's first n - 1 steps: they fill the window and the
            rows they emit (the empty window's zeros) are dropped."""
            carry, _ = jax.lax.scan(
                lambda c, _: env_step(params, c), carry, None, length=n - 1
            )
            return carry._replace(short_rows=jnp.zeros((), jnp.int32))

        # --- shardings + initial carry ---
        key = jax.random.PRNGKey(config.seed + 0xDA)
        k_init, k_run = jax.random.split(key)
        env_state = jax.vmap(env.init)(jax.random.split(k_init, E))
        first_obs = jax.vmap(env.observe)(env_state)
        carry = ActorCarry(
            env_state=env_state,
            # A copy: an env whose observation IS its state would hand the
            # donating rollout one buffer under two leaves.
            obs=jnp.copy(first_obs),
            ou=jnp.zeros((E, act_dim), jnp.float32),
            ep_ret=jnp.zeros((E,), jnp.float32),
            steps=jnp.zeros((), jnp.int32),
            episodes=jnp.zeros((), jnp.int32),
            ret_sum=jnp.zeros((), jnp.float32),
            key=k_run,
            window=(
                nstep_window(E, n, 2 * obs_dim + act_dim + 3)
                if n > 1 else None
            ),
            short_rows=jnp.zeros((), jnp.int32) if n > 1 else None,
            memory=(
                recnet.zero_memory(E, cfg.rnn_hidden, act_dim)
                if recurrent else None
            ),
            seq=(
                seq_window(first_obs, cfg.seq_len, act_dim)
                if recurrent else None
            ),
            state_resets=jnp.zeros((), jnp.int32) if recurrent else None,
        )

        def per_env(tree):
            return jax.tree.map(
                lambda x: P(env_axis, *([None] * (x.ndim - 1))), tree
            )

        carry_spec = ActorCarry(
            env_state=jax.tree.map(lambda _: P(env_axis), env_state),
            obs=P(env_axis, None),
            ou=P(env_axis, None),
            ep_ret=P(env_axis),
            steps=P(),
            episodes=P(),
            ret_sum=P(),
            key=P(),
            window=per_env(carry.window),
            short_rows=None if n == 1 else P(),
            memory=per_env(carry.memory),
            seq=per_env(carry.seq),
            state_resets=P() if recurrent else None,
        )
        self._carry_sharding = mesh_lib.to_named(self.mesh, carry_spec)
        # Rows come out REPLICATED: that is the block sharding
        # DeviceReplay's donated insert expects against its replicated
        # storage (and what makes multi-host replicas bit-identical).
        rows_sharding = NamedSharding(self.mesh, P(None, None))
        # Pure rollout body, kept for composition inside LARGER jitted
        # programs (the fused megastep, parallel/megastep.py): the fused
        # beat calls it on the freshly-updated actor params in the same
        # program, so its rows land with zero extra dispatches. The jitted
        # wrapper below stays the standalone (warmup / unfused) path.
        self._rollout_fn = devactor_rollout
        # Params keep whatever sharding the learner's live tree carries
        # (replicated, or TP-sharded under model_axis > 1): no in_shardings
        # pin, so the pointer-swap refresh never pays a resharding copy.
        self._rollout = jax.jit(
            devactor_rollout,
            out_shardings=(self._carry_sharding, rows_sharding),
            donate_argnums=(1,),
        )
        self._prime = jax.jit(
            prime, out_shardings=self._carry_sharding, donate_argnums=(1,)
        )
        self._carry: ActorCarry = jax.device_put(carry, self._carry_sharding)
        # The exploration scales' ends as the program holds them (header
        # fields devactor_sigma_min / _max); None under OU or SAC.
        self.sigma_ends = None
        if cfg.exploration == "gaussian" and not cfg.sac:
            ladder = np.asarray(sigma_ladder(cfg, E))
            self.sigma_ends = (float(ladder[0]), float(ladder[-1]))

    # --- param refresh (device-side pointer swap) ---

    def set_params(self, actor_params, version: int = 0) -> None:
        """Swap in the learner's LIVE actor params (a device-resident
        pytree reference — nothing is copied or transferred). Callers must
        re-swap after every learner dispatch that donates the TrainState:
        the previously-stored tree is deleted by that donation, and
        dispatching a rollout against it would raise. train.py does this
        at the top of every after_chunk. `version`: the learner's update
        count these parameters stand at (staleness). The first swap of a
        pool that folds n > 1 steps also takes the n - 1 priming steps,
        unless a restored carry brought its window along."""
        self._params = self.rollout_operand(
            self.config, actor_params, version
        )
        self._params_version = int(version)
        if not self._primed:
            self._carry = self._prime(self._params, self._carry)
            self._primed = True
            self._steps += (self.n_step - 1) * self.num_envs

    @staticmethod
    def rollout_operand(config: DDPGConfig, actor_params, version: int):
        """What the rollout program takes as its parameters: the policy's
        tree, and for a pixel configuration the learner step beside it (the
        scheduled noise scale reads it: an argument, so no recompile)."""
        if not config.pixels:
            return actor_params
        return {**actor_params, "learner_step": np.int32(version)}

    @property
    def pending_rows(self) -> int:
        """Env steps taken whose rows are still in the envs' n-step windows:
        in no ring yet, and counted by steps_done."""
        return (self.n_step - 1) * self.num_envs if self._primed else 0

    def note_dispatch(self, newest_version: int) -> None:
        """One rollout is about to read the stored parameters while the
        learner stands at `newest_version` updates (run_chunk and the fused
        beat's caller both say so)."""
        lag = max(0, int(newest_version) - self._params_version)
        self._stale[0] += lag
        self._stale[1] += 1
        self._stale[2] = max(self._stale[2], lag)

    def staleness(self) -> dict:
        """The host pool's two keys, for a run whose only actors are these:
        learner updates between the parameters a rollout read and the newest
        when it was dispatched, over the interval's dispatches."""
        total, n, worst = self._stale
        self._stale = [0, 0, 0]
        return {
            "staleness_mean": total / n if n else 0.0,
            "staleness_max": worst,
        }

    # --- driving ---

    def run_chunk(self, replay, newest_version: int = 0) -> int:
        """One rollout dispatch: K scan steps x E envs -> [K*E, D] rows ->
        donated scatter into `replay` (DeviceReplay.insert_device_rows).
        Returns rows produced. `newest_version`: the learner's update count
        now (note_dispatch). Dispatch-time failures with the carry
        intact restart bounded (module docstring failure contract)."""
        if self._params is None:
            raise DeviceActorError(
                "set_params() must install the learner's live actor params "
                "before the first rollout dispatch"
            )
        while True:
            try:
                # Chaos site ticks BEFORE the dispatch consumes the donated
                # carry, so an injected crash always leaves it retryable.
                if self._fault is not None:
                    self._fault.tick()
                t0 = time.perf_counter()
                with trace.span(
                    "devactor_rollout",
                    rows=self.rows_per_chunk, envs=self.num_envs,
                ):
                    carry, rows = self._rollout(self._params, self._carry)
                    self._carry = carry
                    replay.insert_device_rows(rows)
                dt = time.perf_counter() - t0
            except Exception as e:  # NOT BaseException: Ctrl-C must abort
                if not self._recoverable(e):
                    raise DeviceActorError(
                        "device-actor rollout failed past the restart "
                        "budget"
                    ) from e
                continue
            self._stats.record_chunk(self.rows_per_chunk, dt)
            self.note_dispatch(newest_version)
            self._dispatches += 1
            self._steps += self.rows_per_chunk
            self._rows_emitted += self.rows_per_chunk
            return self.rows_per_chunk

    def _recoverable(self, exc: Exception) -> bool:
        """Bounded-restart policy: recover only while the budget holds AND
        the donated carry is still intact (a failure after donation
        consumed the buffers cannot be retried against deleted arrays —
        the run_sample_chunk fallback's discipline). Single-process ONLY:
        on a multi-host mesh the rollout+insert are global SPMD programs,
        and a per-process retry would enqueue extra programs on THIS
        process alone — forking the pod's per-process device-op order
        (the docs/TRANSFER.md invariant). There the failure must surface
        immediately so the pod deadline/abort contract (PodPeerLost,
        exit 76) handles it pod-wide."""
        if jax.process_count() > 1:
            return False
        if self._restarts >= self._max_restarts:
            return False
        if any(
            getattr(leaf, "is_deleted", lambda: False)()
            for leaf in jax.tree.leaves(self._carry)
        ):
            return False
        self._restarts += 1
        trace.instant("devactor_restart", n=self._restarts)
        print(
            f"[devactor] rollout dispatch failed ({exc!r}); restarting "
            f"({self._restarts}/{self._max_restarts})",
            file=sys.stderr, flush=True,
        )
        return True

    # --- fused-megastep composition (parallel/megastep.py) ---

    @property
    def rollout_fn(self):
        """The pure rollout body — (params, carry) -> (carry, rows[K*E, D])
        — for composition inside the fused megastep's beat program. Same
        function the standalone jit wraps, so the fused and unfused row
        streams are bit-identical for the same params/carry/key."""
        return self._rollout_fn

    def absorb_fused_chunk(self, carry: ActorCarry, dur_s: float,
                           beats: int = 1) -> None:
        """Install the rollout carry returned by a fused megastep beat and
        advance the host counters exactly as run_chunk would. The rollout
        ran INSIDE the beat program, so there is no separate dispatch to
        time — dur_s is the whole beat, and devactor_chunk_ms equals the
        fused beat time in fused mode (docs/FUSED_BEAT.md). A B-beat
        superstep passes beats=B: one dispatch that rolled out B chunks
        (step accounting scales; the chunk timer records the whole
        superstep as one dispatch, so devactor_chunk_ms reads as the
        superstep time — the amortization IS the point)."""
        self._carry = carry
        self._stats.record_chunk(self.rows_per_chunk * beats, dur_s)
        self.note_dispatch(self._params_version)  # the beat's own update
        self._dispatches += 1
        self._steps += self.rows_per_chunk * beats
        self._rows_emitted += self.rows_per_chunk * beats

    # --- rollout-state checkpointing (docs/DEVICE_ACTORS.md) ---

    def carry_state_dict(self) -> dict:
        """Host snapshot of the rollout carry — env state, observations,
        OU noise, per-env episode accumulators, the step/episode counters,
        and the PRNG key — as flat numpy leaves keyed by tree position
        (the carry is a fixed NamedTuple for a given config, so position
        is a stable identity). Rides the checkpoint as a sidecar
        (checkpoint.py devactor_carry.npz, covered by the manifest) so a
        resumed device-actor run CONTINUES its E episodes instead of
        restarting them. One bounded d2h, called at checkpoint cadence
        only.

        Multi-host with the env axis sharded over processes: no single
        writer can pull shards it doesn't address — returns None (the
        checkpoint simply omits the sidecar and a resumed run starts
        fresh episodes, the pre-PR-10 behavior), same single-writer
        limitation as the multi-host sharded replay snapshot
        (docs/REPLAY_SHARDING.md)."""
        leaves = jax.tree.leaves(self._carry)
        if any(
            not getattr(leaf, "is_fully_addressable", True)
            for leaf in leaves
        ):
            return None
        return {
            f"leaf_{i}": np.asarray(jax.device_get(leaf))
            for i, leaf in enumerate(leaves)
        }

    def load_carry_state(self, state: dict) -> bool:
        """Restore a carry_state_dict snapshot into the live carry
        (shape/dtype-validated leaf by leaf). Returns False — with a loud
        note, episodes then start fresh — when the snapshot does not
        match this pool's carry tree (changed env, E, or algorithm
        family): a mismatched resume must degrade to the pre-checkpoint
        behavior, not crash the run. On success the interval episode
        mirrors re-sync so the first snapshot() after resume reports
        deltas, not the whole restored history."""
        leaves, treedef = jax.tree.flatten(self._carry)
        restored = []
        for i, ref in enumerate(leaves):
            arr = state.get(f"leaf_{i}")
            if (
                arr is None
                or tuple(arr.shape) != tuple(ref.shape)
                or np.dtype(arr.dtype) != np.dtype(ref.dtype)
            ):
                print(
                    f"[devactor] checkpointed rollout state does not match "
                    f"this config's carry (leaf {i}: "
                    f"{None if arr is None else (arr.shape, str(arr.dtype))}"
                    f" vs {(tuple(ref.shape), str(ref.dtype))}); starting "
                    "fresh episodes",
                    file=sys.stderr, flush=True,
                )
                return False
            restored.append(arr)
        if len(state) > len(leaves):
            print(
                "[devactor] checkpointed rollout state has extra leaves; "
                "starting fresh episodes",
                file=sys.stderr, flush=True,
            )
            return False
        carry = jax.tree.unflatten(treedef, [jnp.asarray(a) for a in restored])
        self._carry = jax.device_put(carry, self._carry_sharding)
        self._primed = True  # the window came with the carry
        self._eps_seen = int(jax.device_get(self._carry.episodes))
        self._ret_seen = float(jax.device_get(self._carry.ret_sum))
        if self.n_step > 1:
            self._short_seen = int(jax.device_get(self._carry.short_rows))
        # NOTE: the host step mirror (steps_done) stays at 0 — restored
        # production is already counted by the trainer's env_steps_offset,
        # and double-counting would eat the remaining env-step budget. The
        # DEVICE counter (carry.steps) keeps its cumulative value, which
        # is exactly what the uniform-warmup gate needs to stay closed.
        trace.instant(
            "devactor_carry_restored",
            steps=int(jax.device_get(self._carry.steps)),
            episodes=self._eps_seen,
        )
        return True

    # --- host-side views ---

    @property
    def steps_done(self) -> int:
        """Env steps produced so far (host counter — dispatches * K * E;
        identical on every process, so multi-host budget math may use it)."""
        return self._steps

    @property
    def restarts(self) -> int:
        return self._restarts

    def snapshot(self) -> dict:
        """devactor_* observability fields for the train/final records:
        interval rows/s + per-chunk dispatch tails (metrics.DevActorStats)
        plus the episode stats differenced from the carry's cumulative
        device counters — a two-scalar d2h, paid only at log cadence."""
        out = self._stats.snapshot()
        # One d2h for the carry's counters (short_rows: None at n_step 1;
        # state_resets: None but for a recurrent policy).
        eps, ret, short, resets = jax.device_get((
            self._carry.episodes, self._carry.ret_sum, self._carry.short_rows,
            self._carry.state_resets,
        ))
        eps, ret = int(eps), float(ret)
        d_eps = eps - self._eps_seen
        d_ret = ret - self._ret_seen
        self._eps_seen, self._ret_seen = eps, ret
        out["devactor_env_steps"] = self._steps
        out["devactor_episodes"] = eps
        if d_eps > 0:
            out["devactor_episode_return"] = round(d_ret / d_eps, 6)
        out["devactor_restarts"] = self._restarts
        if resets is not None:
            # Episodes' ends at which the pool zeroed a policy memory.
            out["policy_state_resets"] = int(resets)
        if self.n_step > 1:
            # Rows emitted in the interval that hold fewer than n steps
            # (episode ends).
            short, rows = int(short), self._rows_emitted
            d_rows = rows - self._rows_seen
            out["devactor_nstep_short_pct"] = (
                round(100.0 * (short - self._short_seen) / d_rows, 4)
                if d_rows > 0 else 0.0
            )
            self._short_seen, self._rows_seen = short, rows
        return out


# ---------------------------------------------------------------------------
# program-contract analyzer hook (analysis/programs.py; docs/ANALYSIS.md
# "Layer 2")
# ---------------------------------------------------------------------------


def program_specs():
    """The rollout scan as one traced program: 4 vmapped probe envs x a
    chunk of 2, under the 2-device CPU probe mesh. The donated carry must
    alias through in the lowered artifact — a rollout that silently stops
    aliasing would double the env-state HBM every dispatch."""
    from distributed_ddpg_tpu.analysis.programs import (
        BuiltProgram,
        ProgramSpec,
        probe_config,
        probe_mesh,
    )

    def build(tp: bool = False, **kw):
        def _build():
            config = probe_config(
                device_actor_envs=4, device_actor_chunk=2,
                model_axis=2 if tp else 1, actor_backend="device",
                num_actors=0, **kw,
            )
            mesh = probe_mesh(2 if tp else 1)
            pool = DeviceActorPool(config, mesh=mesh)
            from distributed_ddpg_tpu.learner import init_train_state
            from distributed_ddpg_tpu.parallel import mesh as mesh_lib

            state = init_train_state(
                config, pool.obs, pool.env.act_dim, config.seed
            )
            params = state.actor_params
            if config.pixels:
                from distributed_ddpg_tpu.models.pixels import policy_params

                # what set_params hands the rollout, without its priming run
                params = DeviceActorPool.rollout_operand(
                    config, policy_params(state.critic_params, params), 0
                )
            if tp:
                # The live tree's placement: TP-sharded kernels per the
                # rule table, exactly what the pointer-swap refresh hands
                # the rollout (docs/MESH.md).
                params = jax.device_put(
                    params,
                    mesh_lib.to_named(
                        mesh, mesh_lib.net_pspec(params, mesh.shape["model"])
                    ),
                )
            return BuiltProgram(pool._rollout, (params, pool._carry), (1,))
        return _build

    # PQL's rollout: the 3-step window inside the scan (its rows and flags
    # donated and aliased through with the rest of the carry) and the
    # Gaussian ladder in the OU process's place.
    nstep = dict(n_step=3, exploration="gaussian")

    # DrQ-v2's rollout: the convolutional policy on byte frames with the
    # scheduled noise scale read at the learner step beside the parameters,
    # the stand-in's renderer, and the 3-step window on rows of words.
    pixels = dict(
        pixels=True, twin_critic=True, n_step=3, action_insert_layer=0,
        env_id="PixelHumanoidStandIn-v0", encoder_channels=4, feature_dim=8,
    )

    # Recurrent TD3's rollout: one LSTM step of the learner's tree on the
    # memory the carry holds, the window fold that writes a row of the last
    # seq_len steps every step, memory and window zeroed where an episode
    # ends, all donated and aliased through with the rest of the carry.
    recurrent = dict(
        recurrent=True, twin_critic=True, action_insert_layer=0, seq_len=4,
        rnn_hidden=8, obs_embed=4, action_embed=2, reward_embed=2,
        actor_hidden=(8, 8), critic_hidden=(8, 8), exploration="gaussian",
    )

    return [
        ProgramSpec("devactor.rollout", "actors/device_pool.py", build()),
        ProgramSpec(
            "devactor.rollout.tp", "actors/device_pool.py", build(tp=True)
        ),
        ProgramSpec(
            "devactor.rollout.nstep", "actors/device_pool.py", build(**nstep)
        ),
        ProgramSpec(
            "devactor.rollout.pixels", "actors/device_pool.py",
            build(**pixels),
        ),
        ProgramSpec(
            "devactor.rollout.recurrent", "actors/device_pool.py",
            build(**recurrent),
        ),
    ]
