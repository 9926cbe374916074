"""Anakin-style fused training megastep: rollout + ring scatter + sample
+ K learner updates as ONE jitted program per beat (config.fused_beat;
docs/FUSED_BEAT.md; PAPERS.md arXiv 2104.06272, with the device-resident
sample path per the in-network experience-sampling line, arXiv
2110.13506).

The dispatch-per-phase loop (train.py) issues three device programs per
steady-state iteration — the learner chunk, the device-actor rollout, and
the ring insert — with the host's Python between each enqueue. Every
piece already lives in HBM (device actors PR 9, sharded/replicated device
replay PR 10, the scanned learner chunk), so the host round-trips buy
nothing: this module composes the SAME pure bodies those subsystems
expose into one donated-carry program, reducing the host to a metronome
that dispatches beats and reads the one int32 health word.

One fused beat IS one steady-state loop iteration, in the loop's own
order:

  1. **sample + learn** — the learner's XLA-scan sampling chunk
     (`ShardedLearner.pure_scan_sample_fn`: uniform or PER, replicated or
     sharded storage, guarded or unguarded) draws K minibatches from the
     current ring and applies K updates;
  2. **rollout** — the device-actor scan (`DeviceActorPool.rollout_fn`)
     advances E envs for K_env steps with the FRESHLY-UPDATED actor
     params (exactly what the unfused loop's pointer-swap refresh +
     devactor_step does after each chunk);
  3. **scatter** — the rows land in the ring via the replay's pure insert
     body (`DeviceReplay.pure_insert_device_rows_fn`; PER additionally
     max-priority-stamps the landed run, `pure_stamp_fn`).

Because each leg is the IDENTICAL pure function the standalone dispatch
paths jit — same keys, same op order — a fused beat sequence is
bit-identical to the equivalent separate-dispatch sequence for fixed
seeds (tests/test_megastep.py pins uniform + PER, replicated + sharded).

Guardrails ride INSIDE the fused program: the PR-7 GuardState probe
(finite checks, EWMA z-score, tree-select quarantine, bad-row capture)
threads through the composed scan, the beat returns the per-chunk health
word, and `ShardedLearner.note_fused_health` hands it to the existing
host monitor — so `guardrails=True` no longer forces the unfused path;
the fast path is the safe path. (The bad-rollout caveat: a beat whose
learner leg gets quarantined still lands its rollout rows — they were
produced by the pre-rollback policy, which is ordinary replay data and
subject to the same row screen as everything else.)

Multi-host: the beat is one global SPMD program every process dispatches
at the same lockstep point (train.py drives it exactly where the chunk
dispatch sat), so per-process device-op order cannot fork; the lockstep /
shard_exchange ingest beats for HOST rows still ride the transfer
scheduler's ordered lane BETWEEN fused beats (ingest_once is unchanged).

Failure contract: the beat donates its whole carry (TrainState, sampling
key, ring storage/ptr/size, rollout carry, PER priorities, GuardState) at
dispatch, so there is no bounded-restart retry — a dispatch failure
surfaces immediately (the run_sample_chunk fallback's
donation-discipline, without the kernel's degrade leg: every composed
body is the already-proven XLA scan path). Rebuilds are automatic: the
learner's LR-backoff / support-expansion program rebuilds bump
`programs_version`, and the next run_beat recomposes against the fresh
bodies (one XLA recompile, same allowance discipline as the learner's
own rebuild).
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.learner import StepOutput, metric_keys
from distributed_ddpg_tpu.metrics import FusedBeatStats


def build_beat_body(learner, pool, replay, per: bool, guard: bool,
                    rows_per_beat: int):
    """The pure fused-beat body and its jit contract for one
    (per, guard) variant: `(beat, in_shardings, out_shardings,
    donate_argnums)`. FusedMegastep jits it directly (one beat per
    dispatch); parallel/superstep.py composes the SAME body B times
    inside one lax.fori_loop — sharing the construction is what makes
    superstep-vs-sequential bit-identity structural rather than
    coincidental."""
    L = learner
    mesh = L.mesh
    m = int(rows_per_beat)
    insert_fn = replay.pure_insert_device_rows_fn(m)
    stamp_fn = replay.pure_stamp_fn(m) if per else None
    rollout_fn = pool.rollout_fn
    sample_fn = L.pure_scan_sample_fn(per)

    replicated = NamedSharding(mesh, P())
    # The ring goes in and comes back in the Format its owner holds it in
    # (replay/device.py ring_format): the sharding, plus on the TPU the
    # row-major layout where the row's width picks it.
    storage_sharding = replay.storage_format
    prio_sharding = NamedSharding(
        mesh, P("data") if replay.sharded else P(None)
    )
    carry_sharding = pool._carry_sharding
    out_step = StepOutput(
        state=L._state_sharding,
        td_errors=NamedSharding(mesh, P(None, "data")),
        metrics={k: replicated for k in metric_keys(L.config)},
    )

    # The beat bodies below are the loop iteration verbatim: learn on
    # the current ring, roll out with the updated params, scatter.
    # `ptr` is threaded through untouched by the learner leg; PER
    # stamps from the PRE-insert pointer (the insert_device_rows
    # ordering). `nkey`, every variant's last argument, is the base key
    # of the learner's noise stream (ShardedLearner._noise_key; None, an
    # empty pytree, where the algorithm draws none), handed on to the
    # chunk body: a beat's text holds nothing derived from config.seed.
    if not per and not guard:

        def beat(state, key, storage, ptr, size, carry, nkey):
            out, key = sample_fn(state, key, storage, size, nkey)
            carry, rows = rollout_fn(out.state.actor_params, carry)
            storage, ptr, size = insert_fn(storage, rows, ptr, size)
            return out, key, storage, ptr, size, carry

        in_sh = (L._state_sharding, replicated, storage_sharding,
                 replicated, replicated, carry_sharding, replicated)
        out_sh = (out_step, replicated, storage_sharding,
                  replicated, replicated, carry_sharding)
        donate = (0, 1, 2, 3, 4, 5)
    elif not per and guard:

        def beat(state, key, storage, ptr, size, carry, g, nkey):
            out, key, g, health, bad_idx = sample_fn(
                state, key, storage, size, g, nkey
            )
            carry, rows = rollout_fn(out.state.actor_params, carry)
            storage, ptr, size = insert_fn(storage, rows, ptr, size)
            return (out, key, storage, ptr, size, carry, g, health,
                    bad_idx)

        in_sh = (L._state_sharding, replicated, storage_sharding,
                 replicated, replicated, carry_sharding, replicated,
                 replicated)
        out_sh = (out_step, replicated, storage_sharding, replicated,
                  replicated, carry_sharding, replicated, replicated,
                  replicated)
        donate = (0, 1, 2, 3, 4, 5, 6)
    elif per and not guard:

        def beat(state, key, storage, ptr, size, carry, priorities,
                 maxp, beta, alpha, eps, nkey):
            out, key, priorities, maxp = sample_fn(
                state, key, storage, size, priorities, maxp, beta,
                alpha, eps, nkey,
            )
            carry, rows = rollout_fn(out.state.actor_params, carry)
            old_ptr = ptr
            storage, ptr, size = insert_fn(storage, rows, ptr, size)
            priorities = stamp_fn(priorities, maxp, old_ptr)
            return (out, key, storage, ptr, size, carry, priorities,
                    maxp)

        in_sh = (L._state_sharding, replicated, storage_sharding,
                 replicated, replicated, carry_sharding, prio_sharding,
                 replicated, replicated, replicated, replicated,
                 replicated)
        out_sh = (out_step, replicated, storage_sharding, replicated,
                  replicated, carry_sharding, prio_sharding,
                  replicated)
        donate = (0, 1, 2, 3, 4, 5, 6)
    else:

        def beat(state, key, storage, ptr, size, carry, priorities,
                 maxp, beta, alpha, eps, g, nkey):
            out, key, priorities, maxp, g, health, bad_idx = sample_fn(
                state, key, storage, size, priorities, maxp, beta,
                alpha, eps, g, nkey,
            )
            carry, rows = rollout_fn(out.state.actor_params, carry)
            old_ptr = ptr
            storage, ptr, size = insert_fn(storage, rows, ptr, size)
            priorities = stamp_fn(priorities, maxp, old_ptr)
            return (out, key, storage, ptr, size, carry, priorities,
                    maxp, g, health, bad_idx)

        in_sh = (L._state_sharding, replicated, storage_sharding,
                 replicated, replicated, carry_sharding, prio_sharding,
                 replicated, replicated, replicated, replicated,
                 replicated, replicated)
        out_sh = (out_step, replicated, storage_sharding, replicated,
                  replicated, carry_sharding, prio_sharding,
                  replicated, replicated, replicated, replicated)
        donate = (0, 1, 2, 3, 4, 5, 6, 11)

    return beat, in_sh, out_sh, donate


class FusedMegastep:
    """One jitted beat program over (learner, device-actor pool, device
    replay) — see module docstring. Constructed by train.py when
    config.fused_beat resolves active; drives the live objects' state
    (learner.state/_key/_guard, pool carry, replay ring) exactly as the
    separate dispatches would."""

    def __init__(self, config: DDPGConfig, learner, pool, replay):
        self.config = config
        self.learner = learner
        self.pool = pool
        self.replay = replay
        self.per = bool(config.prioritized)
        self.guard = bool(learner.guard_enabled)
        self.chunk_size = int(learner.chunk_size)   # learner steps / beat
        self.rows_per_beat = int(pool.rows_per_chunk)
        self._stats = FusedBeatStats(seed=config.seed)
        self._build()

    def _build(self) -> None:
        beat, in_sh, out_sh, donate = build_beat_body(
            self.learner, self.pool, self.replay, self.per, self.guard,
            self.rows_per_beat,
        )
        # ring_program: the beat hands the ring back (replay/device.py).
        self._beat = self.replay.ring_program(
            jax.jit(
                beat,
                in_shardings=in_sh,
                out_shardings=out_sh,
                donate_argnums=donate,
            )
        )
        self._donate = donate
        self._learner_version = self.learner.programs_version

    # --- driving ---

    def run_beat(self, beta: Optional[float] = None) -> StepOutput:
        """Dispatch one fused beat against the live learner/pool/replay
        state and install every returned carry piece back where the
        separate dispatches would have left it. Returns the learner
        StepOutput (train.py's after_chunk consumes it unchanged)."""
        L, pool, replay = self.learner, self.pool, self.replay
        if self._learner_version != L.programs_version:
            # The learner rebuilt its chunk bodies (LR backoff, support
            # expansion): recompose the beat against the fresh bodies so
            # fused and unfused always run the same effective config.
            self._build()
        t0 = time.perf_counter()
        with replay.dispatch_lock:
            with trace.span(
                "fused_beat", rows=self.rows_per_beat,
                steps=self.chunk_size,
            ):
                if self.per:
                    scalars = (
                        np.float32(beta), np.float32(replay.alpha),
                        np.float32(replay.eps),
                    )
                    if self.guard:
                        (out, key, storage, ptr, size, carry, prios, maxp,
                         g, health, bad_idx) = self._beat(
                            L.state, L._key, replay.storage, replay.ptr,
                            replay.size, pool._carry, replay.priorities,
                            replay.max_priority, *scalars, L._guard,
                            L._noise_key,
                        )
                        L.note_fused_health(g, health, bad_idx)
                    else:
                        (out, key, storage, ptr, size, carry, prios,
                         maxp) = self._beat(
                            L.state, L._key, replay.storage, replay.ptr,
                            replay.size, pool._carry, replay.priorities,
                            replay.max_priority, *scalars, L._noise_key,
                        )
                    replay.set_per_state(prios, maxp)
                else:
                    if self.guard:
                        (out, key, storage, ptr, size, carry, g, health,
                         bad_idx) = self._beat(
                            L.state, L._key, replay.storage, replay.ptr,
                            replay.size, pool._carry, L._guard,
                            L._noise_key,
                        )
                        L.note_fused_health(g, health, bad_idx)
                    else:
                        out, key, storage, ptr, size, carry = self._beat(
                            L.state, L._key, replay.storage, replay.ptr,
                            replay.size, pool._carry, L._noise_key,
                        )
                L.state = out.state
                L._key = key
                replay.storage, replay.ptr, replay.size = storage, ptr, size
                replay.note_device_rows(self.rows_per_beat)
            dt = time.perf_counter() - t0
        pool.absorb_fused_chunk(carry, dt)
        self._stats.record_beat(self.chunk_size, self.rows_per_beat, dt)
        return out

    # --- host-side views ---

    def snapshot(self) -> dict:
        """fused_* observability fields (metrics.FusedBeatStats;
        docs/OBSERVABILITY.md) for the train/final records."""
        return self._stats.snapshot()

    def example_args(self, beta: float = 1.0):
        """The live argument tuple the beat program traces over — the
        program-contract analyzer hook below feeds it to BuiltProgram."""
        L, pool, replay = self.learner, self.pool, self.replay
        args = [L.state, L._key, replay.storage, replay.ptr, replay.size,
                pool._carry]
        if self.per:
            args += [replay.priorities, replay.max_priority,
                     np.float32(beta), np.float32(replay.alpha),
                     np.float32(replay.eps)]
        if self.guard:
            args.append(L._guard)
        args.append(L._noise_key)
        return tuple(args)


# ---------------------------------------------------------------------------
# program-contract analyzer hook (analysis/programs.py; docs/ANALYSIS.md
# "Layer 2")
# ---------------------------------------------------------------------------


def program_specs():
    """The fused beat family, built tiny (4 probe envs x rollout chunk 2,
    learner chunk 2, 64-row ring) under the 2-device CPU probe mesh:
    uniform + PER x replicated + sharded x guarded + unguarded. The
    guarded/unguarded pair of each shape dispatches at the SAME lockstep
    site (train.py picks per config), so they share a beat_group; the
    donated carry (TrainState + key + ring + rollout carry + priorities +
    GuardState) must alias through the lowered artifact — the whole point
    of a fused beat is NOT paying 2x HBM on its carry."""
    from distributed_ddpg_tpu.analysis.programs import (
        BuiltProgram,
        ProgramSpec,
        probe_config,
        probe_mesh,
    )
    from distributed_ddpg_tpu.actors.device_pool import DeviceActorPool
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.replay.device import (
        DevicePrioritizedReplay,
        DeviceReplay,
    )

    OWNER = "parallel/megastep.py"
    cache = {}

    def megastep(
        guard: bool, per: bool, sharded: bool, tp: bool = False
    ) -> FusedMegastep:
        key = (guard, per, sharded, tp)
        if key not in cache:
            placement = "sharded" if sharded else "replicated"
            config = probe_config(
                actor_backend="device",
                num_actors=0,
                device_actor_envs=4,
                device_actor_chunk=2,
                guardrails=guard,
                prioritized=per,
                replay_sharding=placement,
                fused_chunk="off",
                fused_beat="on",
                model_axis=2 if tp else 1,
            )
            mesh = probe_mesh(2 if tp else 1)
            pool = DeviceActorPool(config, mesh=mesh)
            learner = ShardedLearner(
                config,
                pool.obs_dim,
                pool.act_dim,
                pool.action_scale,
                action_offset=pool.action_offset,
                mesh=mesh,
                chunk_size=2,
                replay_sharding=placement,
            )
            replay_cls = DevicePrioritizedReplay if per else DeviceReplay
            replay = replay_cls(
                64, pool.obs_dim, pool.act_dim, mesh=mesh, block_size=8,
                async_ship=False, replay_sharding=placement,
            )
            cache[key] = FusedMegastep(config, learner, pool, replay)
        return cache[key]

    def build(guard: bool, per: bool, sharded: bool, tp: bool = False):
        def _build():
            ms = megastep(guard, per, sharded, tp)
            return BuiltProgram(ms._beat, ms.example_args(), ms._donate)
        return _build

    specs = []
    for per, kind in ((False, "uniform"), (True, "per")):
        for sharded in (False, True):
            shard_tag = ".sharded" if sharded else ""
            for guard in (False, True):
                tag = ".guarded" if guard else ""
                specs.append(ProgramSpec(
                    f"megastep.beat.{kind}{shard_tag}{tag}",
                    OWNER,
                    build(guard, per, sharded),
                    beat_group=f"megastep-beat-{kind}{shard_tag}",
                ))
        # TP variant (docs/MESH.md): the full fused composition — sharded
        # ring on 'data' x params on 'model' — under the (2, 2) probe
        # mesh. It SHARES the 1D sharded beat's beat_group: the
        # explicitly-staged exchange must match that beat's order (a pod
        # mixing TP degrees would fork), and the group check enforces the
        # cross-variant equality a lone golden diff could quietly drop.
        specs.append(ProgramSpec(
            f"megastep.beat.{kind}.sharded.tp",
            OWNER,
            build(False, per, True, tp=True),
            beat_group=f"megastep-beat-{kind}.sharded",
        ))
    return specs
