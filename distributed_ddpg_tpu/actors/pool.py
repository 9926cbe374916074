"""ActorPool: N async rollout workers feeding one learner (SURVEY.md §1's
"N worker processes ... and 1+ PS processes" topology, minus the PS — params
flow learner->workers through shared memory instead of gRPC pulls).

- Param broadcast: one flat f32 shared-memory array + a version counter.
  Workers poll the version each env step and memcpy on change — the
  TPU-native replacement for the reference's per-step parameter pull
  (SURVEY.md §3.2 'pulls current theta from PS').
- Transitions: workers push batched n-step transitions over an mp.Queue;
  `drain_into(replay)` moves them into the host replay buffer.
- Failure detection (SURVEY.md §5): workers stamp heartbeats; `monitor()`
  respawns any worker that died, went silent past the heartbeat timeout,
  or — config.actor_no_progress_s — kept heartbeating while producing
  zero experience rows (the watchdog's documented actor-side blind spot).
  Actors are stateless given params, so a respawn is lossless except the
  in-flight episode. Respawns back off exponentially per slot, and a
  crash-looping slot (config.quarantine_respawns failures within
  config.quarantine_window_s) is QUARANTINED: the pool logs loudly, stops
  respawning it, and training continues degraded — a respawn stampede of
  doomed workers is strictly worse than one missing actor. After
  config.quarantine_probe_s the slot is PROBED with a single respawn
  attempt: sustained progress (rows delivered + surviving
  quarantine_window_s) un-quarantines it (counter actor_unquarantined),
  a probe failure re-quarantines for another cooldown — a half-capacity
  fleet recovers from transient faults without a run restart.
- Fault injection (config.faults; faults.py): each worker receives its
  slice of the run's FaultPlan at spawn time. One-shot faults arm only the
  slot's FIRST incarnation (recovery must be observable); `crashloop`
  re-arms every incarnation to drive the circuit breaker.

Uses the 'spawn' start method: workers must never inherit the parent's JAX
runtime state.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import queue as queue_mod
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.actors.policy import (
    decode_version,
    flatten_params,
    layout_size,
    layout_of,
)
from distributed_ddpg_tpu.actors.worker import run_worker
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.envs.registry import EnvSpec
from distributed_ddpg_tpu.metrics import ForwardMeter, nstep_counters
from distributed_ddpg_tpu.types import ObsSpec, packed_width

# Reap bound for a worker we just terminate()d: long enough for the OS to
# deliver SIGTERM and tear the process down, short enough that a zombie
# never stalls the supervision tick. Not a config knob — no healthy run
# should ever be tuned by how long killing a dead worker takes.
_TERMINATE_JOIN_S = 2.0


class ActorPool:
    def __init__(
        self,
        config: DDPGConfig,
        spec: EnvSpec,
        num_actors: Optional[int] = None,
        heartbeat_timeout: Optional[float] = None,
    ):
        self.config = config
        self.spec = spec
        self.num_actors = num_actors or config.num_actors
        self.heartbeat_timeout = (
            config.heartbeat_timeout_s
            if heartbeat_timeout is None
            else heartbeat_timeout
        )
        heartbeat_timeout = self.heartbeat_timeout
        if config.actor_throttle_s >= heartbeat_timeout:
            raise ValueError(
                f"actor_throttle_s={config.actor_throttle_s} >= the pool's "
                f"heartbeat timeout ({heartbeat_timeout}s): the throttle "
                "sleep sits between heartbeat stamps, so the monitor would "
                "respawn every worker forever"
            )
        self._ctx = mp.get_context("spawn")
        # A pool without workers (a run on device actors alone) shares no
        # parameters: no layout, an empty array, and start() broadcasts
        # nothing. Such a run's policy need not have a host layout at all.
        self.layout = (
            layout_of(config, spec.obs_dim, spec.act_dim)
            if self.num_actors else []
        )
        self._shared = self._ctx.Array("f", layout_size(self.layout), lock=False)
        self._version = self._ctx.Value("l", 0)
        self._queue = self._ctx.Queue(maxsize=4 * self.num_actors)
        # Transport resolution (config.transport): per-worker C++ SPSC rings
        # in anonymous shared memory when available; mp.Queue otherwise. Row
        # layout: [obs, action, reward, discount, next_obs, version] — the
        # trailing version column carries the param-staleness tag that the
        # queue path sends alongside each batch.
        from distributed_ddpg_tpu import native

        if config.transport == "shm" and not native.available():
            raise ValueError(
                "transport='shm' but the native replay core is unavailable "
                "(no C++ toolchain?); use transport='queue'"
            )
        self.transport = (
            "shm"
            if config.transport in ("auto", "shm") and native.available()
            else "queue"
        )
        self.row_width = packed_width(ObsSpec.of_env(spec), spec.act_dim)
        self._rings = []
        self._ring_bufs = []
        if self.transport == "shm":
            nbytes = native.ShmRing.nbytes(config.shm_ring_rows, self.row_width)
            for _ in range(self.num_actors):
                buf = self._ctx.Array("B", nbytes, lock=False)
                self._ring_bufs.append(buf)
                self._rings.append(
                    native.ShmRing(
                        buf, config.shm_ring_rows, self.row_width, init=True
                    )
                )
        # --- served-actor transport (serve/; docs/SERVING.md) ---
        # config.serve_actors: workers request actions from the learner
        # process's InferenceServer over ONE bounded shared request queue
        # (obs rows are tiny — pickling cost is irrelevant at act()
        # granularity) and each worker gets a private response queue so
        # replies never fan out. The counter array records local-act
        # fallbacks (timeout/overload/dispatch failure — the degraded
        # mode the serve chaos tests pin); the pool only ever READS it.
        self.serving = bool(config.serve_actors)
        self._serve_req = None
        self._serve_resp: List = []
        self._serve_fallbacks = None
        if self.serving:
            self._serve_req = self._ctx.Queue(maxsize=config.serve_queue)
            self._serve_resp = [
                self._ctx.Queue(maxsize=8) for _ in range(self.num_actors)
            ]
            self._serve_fallbacks = self._ctx.Array(
                "l", self.num_actors, lock=False
            )
        # n-step row counters (replay/nstep.py): [rows, short rows] per
        # worker slot, written by the worker at each flush, read here.
        self._nstep_counts = (
            self._ctx.Array("l", 2 * self.num_actors, lock=False)
            if config.n_step > 1
            else None
        )
        # host time of the layered policy's forwards (metrics.ForwardMeter):
        # [seconds, forwards] per worker slot, added to by the worker
        self._forward_times = (
            self._ctx.Array("d", 2 * self.num_actors, lock=False)
            if config.simba
            else None
        )
        self._forward_meter = ForwardMeter()
        self._episodes = self._ctx.Queue(maxsize=16 * self.num_actors)
        self._heartbeat = self._ctx.Array("d", self.num_actors, lock=False)
        self._stop = self._ctx.Value("b", 0)
        self._procs: List[Optional[mp.Process]] = [None] * self.num_actors
        self._respawns = 0
        self._steps_received = 0
        # --- supervised recovery state (one entry per worker slot) ---
        self._plan = config.fault_plan()
        self._broadcast_fault = self._plan.site("pool", "broadcast")
        # pool:monitor:slow@k delays the k-th supervision pass — the
        # "supervisor itself is slow" case: training must tolerate late
        # failure detection, not just fast fault recovery.
        self._monitor_fault = self._plan.site("pool", "monitor")
        self._incarnation = [0] * self.num_actors
        self._fail_times: List[List[float]] = [[] for _ in range(self.num_actors)]
        self._backoff_until = [0.0] * self.num_actors
        self._pending_respawn = [False] * self.num_actors
        self._quarantined = [False] * self.num_actors
        # Quarantine probing (config.quarantine_probe_s): after a
        # cooldown, a quarantined slot gets ONE respawn attempt; sustained
        # progress un-quarantines it, any failure during the probe
        # re-quarantines immediately. A half-capacity fleet whose fault
        # was transient recovers without a run restart.
        self._quarantined_at = [0.0] * self.num_actors
        self._probing = [False] * self.num_actors
        self._probe_t = [0.0] * self.num_actors
        self._unquarantines = 0
        # Zero-rows detector clock: 0.0 = "no rows seen this incarnation";
        # armed lazily at the first observed heartbeat (boot can take many
        # seconds under cold-start contention, and the detector must not
        # count boot time as silence).
        self._last_rows_t = [0.0] * self.num_actors
        # Actual-rows clock: written ONLY when experience is drained from
        # the worker (_note_version) — unlike _last_rows_t, which the
        # zero-rows detector also ARMS at first heartbeat. The probe's
        # sustained-progress check reads this one, so a heartbeating-but-
        # rowless probe can never be mistaken for a recovery.
        self._rows_seen_t = [0.0] * self.num_actors
        # Env-step progress restored from a checkpoint (set by the driver
        # BEFORE start()): counts against the uniform-warmup budget so a
        # resumed run doesn't re-inject warmup_uniform random actions.
        self.env_steps_offset = 0
        # Param-staleness tracking (SURVEY.md §5 'params-staleness per
        # actor'): even version -> learner step at broadcast, pruned to the
        # most recent entries; per-worker staleness updated on drain.
        self._version_steps: Dict[int, int] = {}
        self._last_broadcast_step = 0
        self._staleness = np.zeros(self.num_actors, np.int64)

    # --- lifecycle ---

    def warmup_budget_per_worker(self) -> int:
        """REMAINING per-worker uniform-warmup budget at spawn time: the
        global budget (config.resolved_warmup_uniform) net of checkpoint-
        resume progress and steps already drained — a respawned or resumed
        worker must not re-inject random actions into a trained run's
        replay — split evenly (ceil) across the pool."""
        remaining = max(
            0,
            self.config.resolved_warmup_uniform()
            - self.env_steps_offset
            - self._steps_received,
        )
        return (remaining + self.num_actors - 1) // self.num_actors

    def _spawn(self, worker_id: int) -> None:
        fault_specs = self._plan.for_worker(
            worker_id, incarnation=self._incarnation[worker_id]
        )
        self._incarnation[worker_id] += 1
        p = self._ctx.Process(
            target=run_worker,
            kwargs=dict(
                worker_id=worker_id,
                env_id=self.config.env_id,
                seed=self.config.seed + 1000 * (worker_id + 1) + self._respawns,
                layout=self.layout,
                action_scale=self.spec.action_scale,
                action_offset=self.spec.action_offset,
                action_low=self.spec.action_low,
                action_high=self.spec.action_high,
                shared_params=self._shared,
                param_version=self._version,
                transition_queue=self._queue,
                ring_buf=(
                    self._ring_bufs[worker_id] if self.transport == "shm" else None
                ),
                ring_rows=self.config.shm_ring_rows,
                heartbeat=self._heartbeat,
                stop_flag=self._stop,
                ou_theta=self.config.ou_theta,
                ou_sigma=self.config.ou_sigma,
                ou_dt=self.config.ou_dt,
                n_step=self.config.n_step,
                gamma=self.config.gamma,
                fault_specs=fault_specs,
                throttle_s=self.config.actor_throttle_s,
                gaussian_policy=self.config.gaussian_head,
                squash_policy=not self.config.mpo,
                log_std_min=self.config.sac_log_std_min,
                log_std_max=self.config.sac_log_std_max,
                warmup_uniform=self.warmup_budget_per_worker(),
                episode_queue=self._episodes,
                # Served-actor transport (config.serve_actors; None = the
                # default per-worker act() path).
                serve_request_queue=self._serve_req,
                serve_response_queue=(
                    self._serve_resp[worker_id] if self.serving else None
                ),
                serve_fallbacks=self._serve_fallbacks,
                serve_timeout_s=self.config.serve_timeout_s,
                serve_fallback_s=self.config.serve_fallback_s,
                nstep_counts=self._nstep_counts,
                forward_times=self._forward_times,
                # Flight recorder: workers are separate processes, so each
                # keeps its OWN ring and exports trace_actor<k>.json on
                # clean exit; Perfetto merges the files by pid.
                trace_dir=self.config.trace_dir,
                # Orphan guard (worker.py): the worker compares getppid()
                # against the pool process's REAL pid, captured here at
                # spawn time — a late in-worker getppid() capture races
                # with a pool that dies during worker boot.
                parent_pid=os.getpid(),
            ),
            daemon=True,
            name=f"actor-{worker_id}",
        )
        p.start()
        # 0.0 = "never stamped": the worker is still booting (interpreter +
        # gym/mujoco imports + env build — under N-process cold-start
        # contention this takes many times the solo cost, easily past any
        # fixed timeout). The silent-timeout respawn only arms once the
        # worker's loop stamps its first real heartbeat; until then only
        # the liveness check (real deaths) can respawn it. A worker that
        # hangs FOREVER mid-boot while staying alive is therefore never
        # respawned — accepted trade against the respawn stampede, which
        # was self-sustaining (every respawn re-created the boot stampede
        # that caused the timeout).
        self._heartbeat[worker_id] = 0.0
        self._last_rows_t[worker_id] = 0.0  # re-armed at first heartbeat
        self._rows_seen_t[worker_id] = 0.0
        self._procs[worker_id] = p

    def start(self, actor_params) -> "ActorPool":
        if self.num_actors:
            self.broadcast(actor_params)
        for i in range(self.num_actors):
            self._spawn(i)
        return self

    def stop(self) -> None:
        self._stop.value = 1
        deadline = time.time() + 5.0
        for p in self._procs:
            if p is not None:
                p.join(timeout=max(0.1, deadline - time.time()))
        for p in self._procs:
            if p is not None and p.is_alive():
                p.terminate()

    # --- serving surface (serve/; docs/SERVING.md) ---

    def serve_channels(self):
        """(request_queue, response_queues) for the learner process's
        ServeFront. Only meaningful when config.serve_actors built them."""
        return self._serve_req, self._serve_resp

    def param_source(self):
        """(shared flat-param array, seqlock version) — the broadcast
        buffer the workers poll; the InferenceServer refreshes its policy
        from the same source, so serving needs no second param path."""
        return self._shared, self._version

    def serve_counters(self) -> Dict[str, int]:
        """Served-client fallback total for the serve_* metrics family:
        how many times workers degraded to their local act() path."""
        if self._serve_fallbacks is None:
            return {}
        return {
            "serve_client_fallbacks": int(sum(self._serve_fallbacks)),
        }

    def nstep_counters(self) -> Dict[str, int]:
        """Rows the workers' n-step accumulators emitted and how many of
        them carry fewer than n steps (episode ends, truncation flushes),
        summed over workers since the run began; empty at n_step 1, where
        every row is one step."""
        return {} if self._nstep_counts is None else nstep_counters(self._nstep_counts)

    def policy_forward(self) -> Dict[str, float]:
        """`policy_forward_us` since the last call (metrics.ForwardMeter);
        empty outside config.simba."""
        if self._forward_times is None:
            return {}
        return self._forward_meter.snapshot(self._forward_times)

    # --- param broadcast (learner -> workers) ---

    def broadcast(self, actor_params, learner_step: int = 0) -> None:
        """Seqlock write (SURVEY.md §5 'Race detection'): version goes ODD
        while the flat array is being written, EVEN when it is consistent.
        Workers copy only at even versions and re-check the version after
        the copy, so a torn half-old/half-new parameter vector is never
        acted on.

        `learner_step` stamps which learner step these params come from so
        experience can be attributed a staleness (see staleness())."""
        self._broadcast_fault.tick()
        with trace.span("param_broadcast", learner_step=int(learner_step)):
            flat = flatten_params(actor_params)
            view = np.frombuffer(self._shared, dtype=np.float32)
            self._version.value += 1   # odd: write in progress
            view[:] = flat
            self._version.value += 1   # even: consistent
        self._last_broadcast_step = int(learner_step)
        self._version_steps[self._version.value] = self._last_broadcast_step
        while len(self._version_steps) > 64:
            self._version_steps.pop(next(iter(self._version_steps)))

    def _note_version(self, worker_id: int, version: int) -> None:
        acted_at = self._version_steps.get(version, 0)
        self._staleness[worker_id] = self._last_broadcast_step - acted_at
        # Rows arrived from this worker: feed the zero-rows detector and
        # the probe's sustained-progress clock.
        self._last_rows_t[worker_id] = time.time()
        self._rows_seen_t[worker_id] = self._last_rows_t[worker_id]

    def staleness(self) -> Dict[str, float]:
        """Learner-step staleness of the params behind each worker's most
        recently drained experience: 0 = acting on the latest broadcast."""
        s = self._staleness[: self.num_actors]
        return {
            "staleness_mean": float(s.mean()) if len(s) else 0.0,
            "staleness_max": int(s.max()) if len(s) else 0,
        }

    # --- experience (workers -> replay) ---

    def _rows_to_batch(self, rows: np.ndarray) -> Dict[str, np.ndarray]:
        o, a = self.spec.obs_dim, self.spec.act_dim
        return {
            "obs": rows[:, :o],
            "action": rows[:, o : o + a],
            "reward": rows[:, o + a],
            "discount": rows[:, o + a + 1],
            "next_obs": rows[:, o + a + 2 : 2 * o + a + 2],
        }

    def _pop_ring_batches(self, max_rows: Optional[int]) -> List[tuple]:
        out = []
        remaining = self.config.shm_ring_rows * self.num_actors if max_rows is None else int(max_rows)
        for wid, ring in enumerate(self._rings):
            if remaining <= 0:
                break
            # Cap the request at the ring's current occupancy: pop allocates
            # the full request up front, so asking for the worst case on
            # every drain churns tens of MB of empty buffers.
            avail = len(ring)
            if not avail:
                continue
            rows = ring.pop(min(remaining, avail))
            if rows.shape[0]:
                # The version column tags which param snapshot produced each
                # row; rows are in production order, so the last row carries
                # the freshest tag.
                self._note_version(wid, decode_version(rows[-1, -1]))
                out.append((wid, self._rows_to_batch(rows)))
                self._steps_received += rows.shape[0]
                remaining -= rows.shape[0]
        return out

    def drain_into(self, replay, max_batches: int = 1000, max_rows: Optional[int] = None) -> int:
        """Move pending transitions into replay; returns transitions moved.
        `max_rows` caps the transitions taken (the ingest rate limiter's
        budget); overshoot is at most one queue batch on the queue path."""
        moved = 0
        if self.transport == "shm":
            for _wid, batch in self._pop_ring_batches(max_rows):
                replay.add_batch(
                    batch["obs"],
                    batch["action"],
                    batch["reward"],
                    batch["discount"],
                    batch["next_obs"],
                )
                moved += len(batch["reward"])
            return moved
        for _ in range(max_batches):
            if max_rows is not None and moved >= max_rows:
                break
            try:
                wid, version, batch = self._queue.get_nowait()
            except queue_mod.Empty:
                break
            self._note_version(wid, version)
            replay.add_batch(
                batch["obs"],
                batch["action"],
                batch["reward"],
                batch["discount"],
                batch["next_obs"],
            )
            moved += len(batch["reward"])
        self._steps_received += moved
        return moved

    def drain_batches(
        self, max_batches: int = 1000, max_rows: Optional[int] = None,
        with_sources: bool = False,
    ) -> List:
        """Pop pending transition batches raw (for the device-replay ingest
        path, which packs them itself); returns a list of field dicts — or,
        with_sources=True, of (worker_id, fields) pairs so the guardrails'
        bad-row quarantine (train.py) can attribute non-finite replay rows
        back to the slot that produced them."""
        if self.transport == "shm":
            pairs = self._pop_ring_batches(max_rows)
            return pairs if with_sources else [b for _, b in pairs]
        out = []
        moved = 0
        for _ in range(max_batches):
            if max_rows is not None and moved >= max_rows:
                break
            try:
                wid, version, batch = self._queue.get_nowait()
            except queue_mod.Empty:
                break
            self._note_version(wid, version)
            out.append((wid, batch) if with_sources else batch)
            moved += len(batch["reward"])
        self._steps_received += moved
        return out

    def episode_stats(self) -> List[tuple]:
        out = []
        while True:
            try:
                out.append(self._episodes.get_nowait())
            except queue_mod.Empty:
                return out

    # --- failure detection / elastic recovery (SURVEY.md §5) ---

    def monitor(self) -> Dict[str, int]:
        """Supervise the worker fleet. Call periodically. Detects three
        failure shapes — death, heartbeat silence, and (when
        config.actor_no_progress_s > 0) heartbeating-but-zero-rows — and
        respawns through a per-slot exponential backoff; a slot failing
        config.quarantine_respawns times inside quarantine_window_s is
        quarantined instead of respawned (crash-loop circuit breaker)."""
        self._monitor_fault.tick()
        cfg = self.config
        now = time.time()
        respawned = 0
        for i, p in enumerate(self._procs):
            if self._quarantined[i]:
                # Quarantine probing: after the cooldown, one respawn
                # attempt. The slot leaves quarantine provisionally
                # (_probing) so the normal detectors cover it — but any
                # failure during the probe re-quarantines immediately
                # instead of re-entering the backoff/breaker cycle.
                if (
                    cfg.quarantine_probe_s > 0
                    and now - self._quarantined_at[i] >= cfg.quarantine_probe_s
                ):
                    self._quarantined[i] = False
                    self._probing[i] = True
                    self._probe_t[i] = now
                    self._fail_times[i] = []
                    self._respawns += 1
                    respawned += 1
                    trace.instant("actor_probe", worker=i)
                    print(
                        f"[pool] probing quarantined worker {i} after "
                        f"{cfg.quarantine_probe_s:.0f}s cooldown (single "
                        "respawn attempt)",
                        file=sys.stderr, flush=True,
                    )
                    self._spawn(i)
                continue
            if self._probing[i] and not self._pending_respawn[i]:
                # Probe success = sustained progress: rows delivered since
                # the probe spawn AND a full quarantine_window_s survived.
                if (
                    self._rows_seen_t[i] > self._probe_t[i]
                    and now - self._probe_t[i] >= cfg.quarantine_window_s
                ):
                    self._probing[i] = False
                    self._unquarantines += 1
                    trace.instant("actor_unquarantined", worker=i)
                    print(
                        f"[pool] worker {i} UN-QUARANTINED: sustained "
                        f"progress for {cfg.quarantine_window_s:.0f}s "
                        "after probe — fleet back to "
                        f"{self.num_actors - self.quarantined_count} "
                        "workers",
                        file=sys.stderr, flush=True,
                    )
            if not self._pending_respawn[i]:
                why = self._detect_failure(i, p, now)
                if why is None:
                    continue
                if p is not None and p.is_alive():
                    p.terminate()
                    p.join(timeout=_TERMINATE_JOIN_S)
                self._procs[i] = None
                if self._probing[i]:
                    # The single probe attempt failed: straight back to
                    # quarantine for another cooldown — no backoff loop.
                    self._probing[i] = False
                    self._quarantined[i] = True
                    self._quarantined_at[i] = now
                    trace.instant("actor_probe_failed", worker=i, why=why)
                    print(
                        f"[pool] probe of worker {i} failed ({why}); "
                        "re-quarantined",
                        file=sys.stderr, flush=True,
                    )
                    continue
                window = [
                    t for t in self._fail_times[i]
                    if now - t <= cfg.quarantine_window_s
                ]
                window.append(now)
                self._fail_times[i] = window
                if (
                    cfg.quarantine_respawns > 0
                    and len(window) >= cfg.quarantine_respawns
                ):
                    self._quarantined[i] = True
                    self._quarantined_at[i] = now
                    trace.instant("actor_quarantined", worker=i, why=why,
                                  failures=len(window))
                    print(
                        f"[pool] QUARANTINED worker {i}: {len(window)} "
                        f"failures (last: {why}) within "
                        f"{cfg.quarantine_window_s:.0f}s — respawns "
                        "suspended, training continues degraded on "
                        f"{self.num_actors - self.quarantined_count} "
                        "workers"
                        + (
                            f"; probe in {cfg.quarantine_probe_s:.0f}s"
                            if cfg.quarantine_probe_s > 0
                            else ""
                        ),
                        file=sys.stderr, flush=True,
                    )
                    continue
                backoff = min(
                    cfg.respawn_backoff_s * (2.0 ** (len(window) - 1)),
                    cfg.respawn_backoff_max_s,
                )
                self._backoff_until[i] = now + backoff
                self._pending_respawn[i] = True
                trace.instant("actor_respawn", worker=i, why=why,
                              backoff_s=round(backoff, 3))
            if self._pending_respawn[i] and now >= self._backoff_until[i]:
                self._pending_respawn[i] = False
                self._respawns += 1
                respawned += 1
                self._spawn(i)
        return {
            "respawned": respawned,
            "total_respawns": self._respawns,
            "quarantined": self.quarantined_count,
        }

    def _detect_failure(self, i: int, p, now: float) -> Optional[str]:
        """One worker slot's health check; returns the failure kind or
        None. heartbeat == 0 means the worker never finished booting (see
        _spawn) — the silent timeout and the zero-rows detector are not
        armed yet; real deaths are caught regardless."""
        if p is None or not p.is_alive():
            return "dead"
        hb = self._heartbeat[i]
        if hb <= 0.0:
            return None
        if now - hb > self.heartbeat_timeout:
            return "silent"
        no_progress_s = self.config.actor_no_progress_s
        if no_progress_s > 0.0:
            if self._last_rows_t[i] == 0.0:
                # First heartbeat seen with no rows yet: start the clock
                # here, not at spawn — boot time is not production time.
                self._last_rows_t[i] = now
            elif now - self._last_rows_t[i] > no_progress_s:
                return "no_rows"
        return None

    def quarantine_source(self, worker_id: int, why: str = "numeric") -> bool:
        """Quarantine one slot DIRECTLY — the guardrails' bad-row path
        (train.py): a worker repeatedly feeding non-finite experience is
        poisoning replay even though its process looks healthy, so it goes
        through the same breaker state the crash-loop detector uses
        (loud stderr, training continues degraded, probing un-quarantines
        it after quarantine_probe_s if it comes back clean). Returns False
        when the slot is already quarantined."""
        i = int(worker_id)
        if not 0 <= i < self.num_actors or self._quarantined[i]:
            return False
        p = self._procs[i]
        if p is not None and p.is_alive():
            p.terminate()
            p.join(timeout=_TERMINATE_JOIN_S)
        self._procs[i] = None
        self._probing[i] = False
        self._pending_respawn[i] = False
        self._fail_times[i] = []
        self._quarantined[i] = True
        self._quarantined_at[i] = time.time()
        trace.instant("actor_quarantined", worker=i, why=why)
        print(
            f"[pool] QUARANTINED worker {i} ({why}): repeatedly produced "
            "non-finite experience rows — respawns suspended, training "
            "continues degraded on "
            f"{self.num_actors - self.quarantined_count} workers"
            + (
                f"; probe in {self.config.quarantine_probe_s:.0f}s"
                if self.config.quarantine_probe_s > 0
                else ""
            ),
            file=sys.stderr, flush=True,
        )
        return True

    @property
    def quarantined_count(self) -> int:
        return sum(self._quarantined)

    def recovery_counters(self) -> Dict[str, int]:
        """Cumulative fault-history counters for the metrics JSONL
        (train.py logs them; tools.runs summarize surfaces them)."""
        return {
            "actor_respawns": self._respawns,
            "actor_quarantined": self.quarantined_count,
            "actor_unquarantined": self._unquarantines,
        }

    @property
    def steps_received(self) -> int:
        return self._steps_received
