"""Compile-once multi-beat superstep: B fused beats in ONE lax.fori_loop
program per dispatch (`config.superstep_beats`; docs/FUSED_BEAT.md).

PR 13's fused megastep made one steady-state iteration a single jitted
program, but the host still returns to Python once per beat — dispatch
latency, the stats device_get, and JSONL bookkeeping pace the loop
instead of the hardware (exactly the host-orchestration overhead arXiv
2012.04210 measures dominating accelerator RL loops). Going all the way
Anakin (Podracer, PAPERS.md arXiv 2104.06272) means the training EPOCH
is one dispatch: this module wraps B copies of the megastep's pure beat
body (megastep.build_beat_body — the identical composition, not a
re-implementation) inside one donated-carry `jax.lax.fori_loop`, so
B x (sample + K updates, rollout, ring scatter, guardrail probe) runs
with zero host round-trips and the host returns to Python once per
SUPERSTEP.

Structure: ALL B beats run inside `fori_loop(0, B, body, carry)` — the
carry's StepOutput slots are zero-initialized at trace time (eval_shape;
only out.state, seeded with the real incoming TrainState, feeds
arithmetic). Keeping every beat in the loop body is load-bearing for
bit-identity: the body compiles as its own isolated HLO computation and
gets the same codegen as the standalone jitted beat program, whereas a
beat inlined into the main computation gets cross-optimized with its
surroundings (reassociation/fusion, ULP-level divergence that even an
optimization_barrier does not stop). The traced loop body is jit-free
(the recompile-hazard lint asserts this shape stays jit-free: a nested
jit inside the traced body would re-trace per recomposition and defeat
the compile-once contract).

Stats stop being a per-beat host sync:

- **guarded**: the per-beat cumulative int32 health words stack into a
  device-side `[B, 5]` carry (`.at[i].set` in the loop body) and the
  bad-row index captures into `[B, GUARD_BAD_IDX]`;
  `ShardedLearner.note_fused_health` takes the stacked vectors and
  `poll_health()` pays ONE device_get per superstep — the final row is
  the chunk-end cumulative counters the host monitor differences, and
  the per-row deltas yield the first-bad-beat index the guardrail event
  log surfaces. Quarantine stays per-beat ON DEVICE (the tree-select in
  the probe body is unchanged); host rollback/LR-backoff decisions move
  to superstep granularity.
- **unguarded**: metrics/td_errors of the FINAL beat come out (the only
  ones the cadence ever reads — identical to what B sequential beats
  leave in `out`), so nothing syncs until the JSONL cadence asks.

PER beta anneal: the host precomputes the B per-beat betas as a
float32[B] vector reproducing the sequential schedule (beat b anneals
from `budget + b * rows_per_beat`) and the loop body indexes `betas[i]`
— computing the anneal in f32 on device could round differently and
break the bit-identity oracle.

Multi-host: the superstep is one global SPMD program dispatched at the
SAME lockstep site run_beat occupied; host-row `sync_ship`/ingest beats
still ride the transfer scheduler's ordered lanes BETWEEN supersteps
(folding sync_ship into the loop is explicitly out of scope — it is a
host-mediated transfer and would couple the loop to host scheduling).

Bit-identity oracle: `superstep_beats=B` produces bit-identical
TrainState, ring contents, rollout carry, sampling key, and PER
priorities to B sequential fused beats (tests/test_superstep.py pins
B=1 vs unfused and B=4 vs 4 sequential beats across uniform/PER x
replicated/sharded x guarded/unguarded). Rebuild contract matches the
megastep: a learner `programs_version` bump recomposes the loop body on
the next dispatch (one XLA recompile, same allowance discipline).
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.learner import StepOutput
from distributed_ddpg_tpu.metrics import FusedBeatStats
from distributed_ddpg_tpu.parallel.megastep import build_beat_body


def per_beat_betas(config: DDPGConfig, budget_now: int, beats: int,
                   rows_per_beat: int) -> np.ndarray:
    """The float32[B] PER beta-anneal vector a B-beat superstep consumes:
    entry b is exactly the beta the sequential loop would compute before
    its b-th beat (device rows advance rows_per_beat per beat). Host-side
    numpy on purpose — the train loop's anneal runs in Python floats, and
    replicating it bit-for-bit is part of the superstep oracle."""
    betas = np.empty((beats,), np.float32)
    for b in range(beats):
        frac = min(
            1.0, (budget_now + b * rows_per_beat) / config.total_env_steps
        )
        betas[b] = np.float32(
            config.per_beta + frac * (config.per_beta_final - config.per_beta)
        )
    return betas


class FusedSuperstep:
    """B fused beats in one donated-carry fori_loop program — see module
    docstring. Drop-in sibling of FusedMegastep (train.py constructs one
    or the other from config.superstep_beats); drives the live
    learner/pool/replay state exactly as B sequential run_beat calls
    would, with one dispatch and one host sync point."""

    def __init__(self, config: DDPGConfig, learner, pool, replay,
                 beats: Optional[int] = None):
        self.config = config
        self.learner = learner
        self.pool = pool
        self.replay = replay
        self.per = bool(config.prioritized)
        self.guard = bool(learner.guard_enabled)
        self.beats = int(
            beats if beats is not None else config.superstep_beats
        )
        if self.beats < 1:
            raise ValueError(f"superstep beats must be >= 1, got {beats}")
        self.chunk_size = int(learner.chunk_size)   # learner steps / beat
        self.rows_per_beat = int(pool.rows_per_chunk)
        self._stats = FusedBeatStats(seed=config.seed)
        self._build()

    def _build(self) -> None:
        beat, in_sh, out_sh, donate = build_beat_body(
            self.learner, self.pool, self.replay, self.per, self.guard,
            self.rows_per_beat,
        )
        B = self.beats

        # One composition per (per, guard) variant. EVERY beat runs inside
        # the fori_loop body (range 0..B): the loop body compiles as its
        # own isolated HLO computation, so XLA gives it the same codegen
        # as the standalone jitted beat program — that is what makes the
        # superstep BIT-identical to B sequential run_beat dispatches.
        # (Inlining the first beat into the main computation instead was
        # measurably NOT bit-identical: XLA cross-optimizes an inlined
        # beat with its surroundings — reassociation/fusion at ULP level —
        # and an optimization_barrier does not stop the divergence.)
        # Shape discipline: the pre-loop StepOutput carry slots are
        # zero-initialized from eval_shape (trace-time only, no FLOPs);
        # the body overwrites them every iteration and only out.state —
        # seeded with the REAL incoming state — feeds arithmetic.

        def init_out(shapes, state):
            out0 = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes[0]
            )
            return out0._replace(state=state)

        if not self.per and not self.guard:

            def superstep(state, key, storage, ptr, size, carry, nkey):
                shapes = jax.eval_shape(
                    beat, state, key, storage, ptr, size, carry, nkey
                )
                out0 = init_out(shapes, state)

                def body(i, acc):
                    out, key, storage, ptr, size, carry = acc
                    return beat(
                        out.state, key, storage, ptr, size, carry, nkey
                    )

                return jax.lax.fori_loop(
                    0, B, body, (out0, key, storage, ptr, size, carry)
                )

        elif not self.per and self.guard:

            def superstep(state, key, storage, ptr, size, carry, g, nkey):
                shapes = jax.eval_shape(
                    beat, state, key, storage, ptr, size, carry, g, nkey
                )
                out0 = init_out(shapes, state)
                # Stacked per-beat stats carry: health rows land at [i],
                # bad-row captures pre-filled with the device's "no bad
                # row" sentinel (-1) — one device_get reads the lot.
                hs = jnp.zeros((B,) + shapes[7].shape, shapes[7].dtype)
                bs = jnp.full((B,) + shapes[8].shape, -1, shapes[8].dtype)

                def body(i, acc):
                    out, key, storage, ptr, size, carry, g, hs, bs = acc
                    (out, key, storage, ptr, size, carry, g, h, b) = beat(
                        out.state, key, storage, ptr, size, carry, g, nkey
                    )
                    return (out, key, storage, ptr, size, carry, g,
                            hs.at[i].set(h), bs.at[i].set(b))

                return jax.lax.fori_loop(
                    0, B, body,
                    (out0, key, storage, ptr, size, carry, g, hs, bs),
                )

        elif self.per and not self.guard:

            def superstep(state, key, storage, ptr, size, carry,
                          priorities, maxp, betas, alpha, eps, nkey):
                shapes = jax.eval_shape(
                    beat, state, key, storage, ptr, size, carry,
                    priorities, maxp, betas[0], alpha, eps, nkey,
                )
                out0 = init_out(shapes, state)

                def body(i, acc):
                    (out, key, storage, ptr, size, carry, priorities,
                     maxp) = acc
                    return beat(out.state, key, storage, ptr, size, carry,
                                priorities, maxp, betas[i], alpha, eps, nkey)

                return jax.lax.fori_loop(
                    0, B, body,
                    (out0, key, storage, ptr, size, carry, priorities,
                     maxp),
                )

        else:

            def superstep(state, key, storage, ptr, size, carry,
                          priorities, maxp, betas, alpha, eps, g, nkey):
                shapes = jax.eval_shape(
                    beat, state, key, storage, ptr, size, carry,
                    priorities, maxp, betas[0], alpha, eps, g, nkey,
                )
                out0 = init_out(shapes, state)
                hs = jnp.zeros((B,) + shapes[9].shape, shapes[9].dtype)
                bs = jnp.full((B,) + shapes[10].shape, -1, shapes[10].dtype)

                def body(i, acc):
                    (out, key, storage, ptr, size, carry, priorities, maxp,
                     g, hs, bs) = acc
                    (out, key, storage, ptr, size, carry, priorities, maxp,
                     g, h, b) = beat(
                        out.state, key, storage, ptr, size, carry,
                        priorities, maxp, betas[i], alpha, eps, g, nkey,
                    )
                    return (out, key, storage, ptr, size, carry, priorities,
                            maxp, g, hs.at[i].set(h), bs.at[i].set(b))

                return jax.lax.fori_loop(
                    0, B, body,
                    (out0, key, storage, ptr, size, carry, priorities,
                     maxp, g, hs, bs),
                )

        # The jit contract is the megastep's own per-variant tuple: same
        # argument order, same donation indices; the guarded health/bad
        # outputs simply grow a leading [B] axis (still replicated).
        sup_in = in_sh
        sup_out = out_sh

        # ring_program: the superstep hands the ring back (replay/device.py).
        self._superstep = self.replay.ring_program(
            jax.jit(
                superstep,
                in_shardings=sup_in,
                out_shardings=sup_out,
                donate_argnums=donate,
            )
        )
        self._donate = donate
        self._learner_version = self.learner.programs_version

    # --- driving ---

    def run_superstep(self, betas: Optional[np.ndarray] = None) -> StepOutput:
        """Dispatch B fused beats as one program and install every
        returned carry piece back on the live objects, exactly where B
        sequential run_beat calls would have left them. `betas` is the
        float32[B] PER anneal vector (per_beat_betas); None for uniform.
        Returns the FINAL beat's StepOutput — the one a sequential run's
        last after_chunk would consume."""
        L, pool, replay = self.learner, self.pool, self.replay
        if self._learner_version != L.programs_version:
            # The learner rebuilt its chunk bodies (LR backoff, support
            # expansion): recompose the whole loop body against the fresh
            # bodies — one XLA recompile, the megastep's rebuild contract.
            self._build()
        B = self.beats
        t0 = time.perf_counter()
        with replay.dispatch_lock:
            with trace.span(
                "superstep", beats=B, rows=B * self.rows_per_beat,
                steps=B * self.chunk_size,
            ):
                if self.per:
                    bvec = jnp.asarray(
                        np.broadcast_to(
                            np.asarray(betas, np.float32), (B,)
                        ).copy()
                    )
                    scalars = (
                        bvec, np.float32(replay.alpha),
                        np.float32(replay.eps),
                    )
                    if self.guard:
                        (out, key, storage, ptr, size, carry, prios, maxp,
                         g, health, bad_idx) = self._superstep(
                            L.state, L._key, replay.storage, replay.ptr,
                            replay.size, pool._carry, replay.priorities,
                            replay.max_priority, *scalars, L._guard,
                            L._noise_key,
                        )
                        L.note_fused_health(g, health, bad_idx)
                    else:
                        (out, key, storage, ptr, size, carry, prios,
                         maxp) = self._superstep(
                            L.state, L._key, replay.storage, replay.ptr,
                            replay.size, pool._carry, replay.priorities,
                            replay.max_priority, *scalars, L._noise_key,
                        )
                    replay.set_per_state(prios, maxp)
                else:
                    if self.guard:
                        (out, key, storage, ptr, size, carry, g, health,
                         bad_idx) = self._superstep(
                            L.state, L._key, replay.storage, replay.ptr,
                            replay.size, pool._carry, L._guard,
                            L._noise_key,
                        )
                        L.note_fused_health(g, health, bad_idx)
                    else:
                        (out, key, storage, ptr, size,
                         carry) = self._superstep(
                            L.state, L._key, replay.storage, replay.ptr,
                            replay.size, pool._carry, L._noise_key,
                        )
                L.state = out.state
                L._key = key
                replay.storage, replay.ptr, replay.size = storage, ptr, size
                replay.note_device_rows(self.rows_per_beat, inserts=B)
            dt = time.perf_counter() - t0
        pool.absorb_fused_chunk(carry, dt, beats=B)
        self._stats.record_beat(
            B * self.chunk_size, B * self.rows_per_beat, dt, beats=B,
        )
        return out

    # --- host-side views ---

    def snapshot(self) -> dict:
        """fused_* observability fields (metrics.FusedBeatStats;
        docs/OBSERVABILITY.md) — the superstep reuses the fused family,
        with fused_supersteps/fused_superstep_beats marking the dispatch
        amortization."""
        return self._stats.snapshot()

    def example_args(self, beta: float = 1.0):
        """The live argument tuple the superstep program traces over —
        the program-contract analyzer hook below feeds it to
        BuiltProgram (donation indices match run_superstep's dispatch)."""
        L, pool, replay = self.learner, self.pool, self.replay
        args = [L.state, L._key, replay.storage, replay.ptr, replay.size,
                pool._carry]
        if self.per:
            args += [replay.priorities, replay.max_priority,
                     np.full((self.beats,), beta, np.float32),
                     np.float32(replay.alpha), np.float32(replay.eps)]
        if self.guard:
            args.append(L._guard)
        args.append(L._noise_key)
        return tuple(args)


# ---------------------------------------------------------------------------
# program-contract analyzer hook (analysis/programs.py; docs/ANALYSIS.md
# "Layer 2")
# ---------------------------------------------------------------------------


def program_specs():
    """The superstep family at B=2 (the smallest loop that actually
    iterates), built tiny under the 2-device CPU probe mesh: uniform +
    PER x replicated + sharded x guarded + unguarded, plus the TP
    composition. The donated carry is the megastep's ENLARGED by the
    loop (same donated tuple — the stacked health words are outputs, not
    inputs) and must still alias through the lowered artifact; the
    guarded/unguarded pair of each shape dispatches at the same lockstep
    site, so they share a beat_group exactly like the megastep variants
    (the superstep's collective order is the beat's order twice: once
    for the inline first beat, once for the traced loop body)."""
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

    OWNER = "parallel/superstep.py"
    cache = {}

    def superstep(
        guard: bool, per: bool, sharded: bool, tp: bool = False
    ) -> FusedSuperstep:
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
                superstep_beats=2,
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
            cache[key] = FusedSuperstep(
                config, learner, pool, replay, beats=2
            )
        return cache[key]

    def build(guard: bool, per: bool, sharded: bool, tp: bool = False):
        def _build():
            ss = superstep(guard, per, sharded, tp)
            return BuiltProgram(
                ss._superstep, ss.example_args(), ss._donate
            )
        return _build

    specs = []
    for per, kind in ((False, "uniform"), (True, "per")):
        for sharded in (False, True):
            shard_tag = ".sharded" if sharded else ""
            for guard in (False, True):
                tag = ".guarded" if guard else ""
                specs.append(ProgramSpec(
                    f"superstep.loop.{kind}{shard_tag}{tag}",
                    OWNER,
                    build(guard, per, sharded),
                    beat_group=f"superstep-loop-{kind}{shard_tag}",
                ))
        # TP variant (docs/MESH.md): the carry pspecs — TP-sharded params
        # + 'data'-sharded ring — must survive the fori_loop composition
        # under the (2, 2) probe mesh; shares the 1D sharded loop's
        # beat_group so the staged exchange order cannot fork a pod
        # mixing TP degrees.
        specs.append(ProgramSpec(
            f"superstep.loop.{kind}.sharded.tp",
            OWNER,
            build(False, per, True, tp=True),
            beat_group=f"superstep-loop-{kind}.sharded",
        ))
    return specs
