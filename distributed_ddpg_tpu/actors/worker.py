"""Rollout worker process (SURVEY.md §3.2's per-worker episode loop).

Each worker owns: one env, one OU noise process (per-worker instance, reset
per episode — SURVEY.md §2 #6), one n-step accumulator, and a numpy policy
refreshed from the shared-memory param buffer. It streams n-step transitions
back in batches over an mp.Queue and stamps a heartbeat every loop so the
pool's monitor can respawn it if it dies (SURVEY.md §5 'Failure detection';
the reference has none — a dead TF worker just stalls).

Workers never import jax (see policy.py). `fault_specs` is this worker's
slice of the run's FaultPlan (config.faults; faults.py) — (kind, at_step,
duration_s) tuples applied inline: `crash` raises, `hang` freezes WITHOUT
heartbeats (the silent-timeout respawn path), `stall` keeps heartbeating
but produces nothing (the pool monitor's zero-rows detector), `slow`
throttles env stepping for a bounded window.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np


def run_worker(
    worker_id: int,
    env_id: str,
    seed: int,
    layout,
    action_scale,
    action_offset,
    action_low,
    action_high,
    shared_params,          # mp.Array('f'), flat actor params
    param_version,          # mp.Value('l')
    transition_queue,       # mp.Queue (fallback transport)
    heartbeat,              # mp.Array('d', num_workers)
    stop_flag,              # mp.Value('b')
    ring_buf,               # mp.Array('B') backing a native.ShmRing, or None
    ring_rows: int,
    ou_theta: float,
    ou_sigma: float,
    ou_dt: float,
    n_step: int,
    gamma: float,
    send_every: int = 32,
    fault_specs=(),         # (kind, at_step, duration_s) tuples, sorted by step
    throttle_s: float = 0.0,
    gaussian_policy: bool = False,  # SAC, MPO: sample the policy, no OU noise
    squash_policy: bool = True,     # False: MPO's plain Gaussian, clipped to the box
    log_std_min: float = -5.0,
    log_std_max: float = 2.0,
    warmup_uniform: int = 0,  # uniform-random actions for the first N steps
    episode_queue=None,     # optional mp.Queue for (worker_id, return, length)
    parent_pid: int = 0,    # pool process pid, captured at spawn time
    trace_dir: str = "",    # flight-recorder export dir ("" = off)
    serve_request_queue=None,   # served-actor transport (config.serve_actors):
    serve_response_queue=None,  # shared req queue + this worker's resp queue
    serve_fallbacks=None,       # mp.Array('l'): local-act fallback counters
    serve_timeout_s: float = 1.0,
    serve_fallback_s: float = 5.0,
    nstep_counts=None,          # mp.Array('l', 2 * num_workers), or None
    forward_times=None,         # mp.Array('d', 2 * num_workers), or None
) -> None:
    # Workers are CPU-only by construction; make BLAS behave in many procs.
    os.environ.setdefault("OMP_NUM_THREADS", "1")

    # NOTE: no heartbeat stamp until the loop below — heartbeat 0.0 is the
    # pool's "still booting" sentinel (ActorPool._spawn): under N-process
    # cold-start contention the imports + env build here take many times
    # the solo cost, and stamping mid-boot would arm the silent-timeout
    # respawn before the worker can possibly meet it.

    from distributed_ddpg_tpu import trace
    from distributed_ddpg_tpu.actors.policy import (
        NumpyPolicy,
        encode_version,
        seqlock_snapshot,
    )
    from distributed_ddpg_tpu.envs import make
    from distributed_ddpg_tpu.ops.noise import OUNoise
    from distributed_ddpg_tpu.replay.nstep import NStepAccumulator

    # Flight recorder (trace.py): a worker is its own interpreter, so it
    # owns its own ring and exports a per-process file on exit — Perfetto
    # merges by pid. Spans cover flushes (transport waits show up as long
    # actor_flush spans = learner-side backpressure) and episode instants.
    if trace_dir:
        trace.configure(capacity=8192)

    env = make(env_id, seed=seed)
    act_dim = len(np.atleast_1d(action_low))
    policy = NumpyPolicy(
        layout,
        action_scale,
        action_offset,
        gaussian=gaussian_policy,
        stochastic=gaussian_policy,
        seed=seed,
        log_std_min=log_std_min,
        log_std_max=log_std_max,
        squash=squash_policy,
    )
    # SAC explores by sampling its own tanh-Gaussian; the OU process is
    # zeroed (sigma=0 keeps the loop shape identical at no cost).
    noise = OUNoise(
        (act_dim,),
        theta=ou_theta,
        sigma=0.0 if gaussian_policy else ou_sigma,
        dt=ou_dt,
        seed=seed,
    )
    nstep = NStepAccumulator(n_step, gamma)
    if nstep_counts is not None:
        # This slot's (rows, short rows) so far: a respawned worker counts on.
        nstep.rows = nstep_counts[2 * worker_id]
        nstep.short_rows = nstep_counts[2 * worker_id + 1]
    warmup_rng = np.random.default_rng(seed + 7919)  # uniform-warmup draws
    flat_view = np.frombuffer(shared_params, dtype=np.float32)
    flat_scratch = np.empty_like(flat_view)
    seen_version = -1

    # shm transport: attach to the pool's ring (the parent already ran
    # ring_init; the cached .so compiles in the parent so this load is a
    # dlopen, not a g++ run). The ring and the queue never mix — the pool
    # drains whichever transport it configured.
    ring = None
    if ring_buf is not None:
        from distributed_ddpg_tpu import native

        obs_dim = layout[0][0][0]  # first layer w is (obs_dim, hidden)
        ring = native.ShmRing(
            ring_buf, ring_rows, 2 * obs_dim + act_dim + 3, init=False
        )

    pending: list = []
    carry = None  # rows the ring had no room for on the last flush

    def local_mu(o: np.ndarray) -> np.ndarray:
        """One forward of the local policy; a layered policy's is a span
        of the flight recorder and is timed (pool.policy_forward)."""
        if forward_times is None:
            return policy(o)[0]
        t0 = time.perf_counter()
        with trace.span("policy_forward"):
            mu = policy(o)[0]
        forward_times[2 * worker_id] += time.perf_counter() - t0
        forward_times[2 * worker_id + 1] += 1
        return mu

    def maybe_refresh():
        """Seqlock read (policy.seqlock_snapshot; see ActorPool.broadcast):
        a torn or mid-write snapshot is discarded and the previous
        consistent params keep acting until the next step."""
        nonlocal seen_version
        v = seqlock_snapshot(shared_params, param_version, flat_scratch,
                             seen_version)
        if v is not None:
            policy.load_flat(flat_scratch)
            seen_version = v

    def flush():
        # seen_version tags which param snapshot produced this experience —
        # the pool converts it to learner-step staleness (SURVEY.md §5
        # 'params-staleness per actor').
        with trace.span("actor_flush", rows=len(pending)):
            _flush_impl()
        if nstep_counts is not None:
            nstep_counts[2 * worker_id] = nstep.rows
            nstep_counts[2 * worker_id + 1] = nstep.short_rows

    def _flush_impl():
        nonlocal carry
        if ring is not None:
            if pending:
                n = len(pending)
                rows = np.empty((n, ring.width), np.float32)
                o = pending[0][0].shape[-1]
                rows[:, :o] = np.stack([p[0] for p in pending])
                rows[:, o : o + act_dim] = np.stack([p[1] for p in pending])
                rows[:, o + act_dim] = [p[2] for p in pending]
                rows[:, o + act_dim + 1] = [p[3] for p in pending]
                rows[:, o + act_dim + 2 : 2 * o + act_dim + 2] = np.stack(
                    [p[4] for p in pending]
                )
                rows[:, -1] = encode_version(seen_version)
                pending.clear()
                carry = rows if carry is None else np.concatenate([carry, rows])
            # Backpressure mirrors mp.Queue.put: block (stamping the
            # heartbeat so the monitor doesn't respawn a merely-throttled
            # worker) until the learner drains the ring. This throttles env
            # stepping instead of dropping experience.
            while carry is not None and not stop_flag.value:
                if parent_pid and os.getppid() != parent_pid:
                    return  # orphaned mid-backpressure: drainer is gone
                accepted = ring.push(carry)
                carry = carry[accepted:] if accepted < carry.shape[0] else None
                if carry is not None:
                    heartbeat[worker_id] = time.time()
                    time.sleep(0.001)
            return
        if not pending:
            return
        batch = {
            "obs": np.stack([p[0] for p in pending]),
            "action": np.stack([p[1] for p in pending]),
            "reward": np.asarray([p[2] for p in pending], np.float32),
            "discount": np.asarray([p[3] for p in pending], np.float32),
            "next_obs": np.stack([p[4] for p in pending]),
        }
        # The queue is BOUNDED (pool maxsize): a blocking put() on a full
        # queue whose drainer died would hang past the orphan guard, so
        # mirror the ring path — bounded waits with the guard between them.
        import queue as queue_mod

        delivered = False
        while not stop_flag.value:
            if parent_pid and os.getppid() != parent_pid:
                return  # orphaned mid-backpressure: drainer is gone
            try:
                transition_queue.put((worker_id, seen_version, batch), timeout=0.1)
                delivered = True
                break
            except queue_mod.Full:
                heartbeat[worker_id] = time.time()
        if not delivered:
            # Clean shutdown (stop_flag set before or during the loop):
            # one non-blocking attempt delivers the tail when there's room;
            # a full queue drops it — bounded loss (< send_every rows),
            # matching the ring path's shutdown behavior.
            try:
                transition_queue.put_nowait((worker_id, seen_version, batch))
            except queue_mod.Full:
                pass
        pending.clear()

    # --- served acting (serve/; docs/SERVING.md) ---
    # With the serve transport attached, mu(s) comes from the learner
    # process's InferenceServer (dynamic batching across the fleet); the
    # local policy mirror stays loaded as the FALLBACK — any failure to
    # get a served action (queue full, timeout, dispatch error) degrades
    # to it for serve_fallback_s. The failure contract: a stalled or dead
    # serving stack costs latency, never a deadlock (chaos tests pin it).
    import queue as serve_queue_mod

    # Request ids start at a per-incarnation random 48-bit offset, and any
    # replies already sitting in the response queue are drained: the pool
    # reuses the SAME response queue across respawns of this slot, so a
    # late reply addressed to a dead incarnation must never collide with a
    # fresh incarnation's rid and deliver an action computed for a
    # different observation.
    serve_rid = int.from_bytes(os.urandom(6), "little")
    serve_down_until = 0.0
    if serve_response_queue is not None:
        while True:
            try:
                serve_response_queue.get_nowait()
            except Exception:
                break

    def _serve_degrade() -> None:
        nonlocal serve_down_until
        serve_down_until = time.time() + serve_fallback_s
        if serve_fallbacks is not None:
            serve_fallbacks[worker_id] += 1

    def served_mu(o: np.ndarray) -> np.ndarray:
        """One served action request, bounded by serve_timeout_s; the
        local mirror answers whenever the served path cannot."""
        nonlocal serve_rid
        if time.time() < serve_down_until:
            return local_mu(o)
        serve_rid += 1
        try:
            serve_request_queue.put_nowait(
                (worker_id, serve_rid, np.asarray(o, np.float32))
            )
        except serve_queue_mod.Full:
            _serve_degrade()
            return local_mu(o)
        deadline = time.time() + serve_timeout_s
        while time.time() < deadline and not stop_flag.value:
            if parent_pid and os.getppid() != parent_pid:
                return local_mu(o)  # orphaned: server is gone
            try:
                rid, action = serve_response_queue.get(timeout=0.05)
            except serve_queue_mod.Empty:
                # Keep the heartbeat warm: a served wait is bounded and
                # healthy, not a silent worker.
                heartbeat[worker_id] = time.time()
                continue
            if rid != serve_rid:
                continue  # stale reply from a request we already gave up on
            if action is None:
                _serve_degrade()  # server shed or failed this request
                return local_mu(o)
            return np.asarray(action, np.float32)
        _serve_degrade()
        return local_mu(o)

    # --- scripted faults (faults.py; see module docstring) ---
    faults = sorted(fault_specs, key=lambda t: t[1])
    fault_i = 0
    slow_until, slow_sleep = 0, 0.0
    hung = False

    def _freeze(stamp_heartbeat: bool) -> None:
        """Injected hang/stall: park until the pool terminates this process
        (the recovery under test) or a clean stop/orphaning ends the run.
        `hang` parks WITHOUT heartbeats — the silent-timeout respawn path;
        `stall` keeps stamping them while producing nothing — the zero-rows
        detector path (pool.monitor)."""
        while not stop_flag.value:
            if parent_pid and os.getppid() != parent_pid:
                return
            if stamp_heartbeat:
                heartbeat[worker_id] = time.time()
            time.sleep(0.05)

    def apply_faults(step: int) -> bool:
        """Fire faults due at `step`; returns True if the worker must exit
        (it was hung/stalled and released by stop/orphaning)."""
        nonlocal fault_i, slow_until, slow_sleep
        while fault_i < len(faults) and faults[fault_i][1] <= step:
            kind, _, dur = faults[fault_i]
            fault_i += 1
            if kind == "crash":
                from distributed_ddpg_tpu.faults import InjectedFault

                raise InjectedFault(
                    f"injected crash in worker {worker_id} at step {step}"
                )
            if kind in ("hang", "stall"):
                _freeze(stamp_heartbeat=(kind == "stall"))
                return True
            if kind == "slow":
                from distributed_ddpg_tpu.faults import SLOW_FAULT_STEPS

                slow_until = step + SLOW_FAULT_STEPS
                slow_sleep = dur
        if step < slow_until and slow_sleep > 0.0:
            time.sleep(slow_sleep)
        return False

    maybe_refresh()
    obs, _ = env.reset(seed=seed)
    noise.reset()
    ep_return, ep_len, total_steps = 0.0, 0, 0

    # Orphan guard: stop_flag is only ever set by pool.stop(), which a
    # hard-killed pool process (SIGKILL, watchdog os._exit) never runs —
    # daemon=True also doesn't help there, since the interpreter's atexit
    # cleanup is skipped. A reparented worker (getppid no longer the pool
    # pid passed at spawn — capturing getppid() here instead would race
    # with a pool that dies during worker boot) has no consumer left, so
    # it must exit — without flush(), whose ring backpressure would
    # otherwise block forever on the dead drainer.
    orphaned = False
    while not stop_flag.value:
        if parent_pid and os.getppid() != parent_pid:
            orphaned = True
            break
        heartbeat[worker_id] = time.time()
        maybe_refresh()
        if throttle_s > 0.0:
            # Staleness-sweep experiment knob (config.actor_throttle_s):
            # slow env production so the learner can saturate the ratio
            # caps on slow hosts. Sleep sits BEFORE the step so the
            # heartbeat above keeps the respawn monitor quiet.
            time.sleep(throttle_s)
        if total_steps < warmup_uniform:
            # Uniform-random warmup (config.warmup_uniform_steps — SAC's
            # start_steps): broad seed data before the policy takes over.
            action = warmup_rng.uniform(action_low, action_high).astype(
                np.float32
            )
        else:
            mu = (
                served_mu(obs)
                if serve_request_queue is not None
                else local_mu(obs)
            )
            action = mu + noise() * np.asarray(action_scale, np.float32)
        action = np.clip(action, action_low, action_high).astype(np.float32)
        next_obs, reward, terminated, truncated, _ = env.step(action)
        done = terminated  # truncation bootstraps: discount stays gamma^n
        pending.extend(
            nstep.push(obs[None], action[None], [reward], [done], next_obs[None])
        )
        ep_return += reward
        ep_len += 1
        total_steps += 1
        obs = next_obs

        if apply_faults(total_steps):
            hung = True  # parked by an injected hang/stall, then released
            break

        if terminated or truncated:
            # Flush the truncation tail through the accumulator so no
            # experience is stranded, then reset per-episode state.
            if truncated and not terminated:
                pending.extend(_flush_truncated(nstep, next_obs))
            trace.instant(
                "episode", ret=round(ep_return, 3), length=ep_len
            )
            if episode_queue is not None:
                try:
                    episode_queue.put_nowait((worker_id, ep_return, ep_len))
                except Exception:
                    pass
            obs, _ = env.reset()
            noise.reset()
            nstep.reset()
            ep_return, ep_len = 0.0, 0

        if len(pending) >= send_every:
            flush()

    # Orphaned workers skip the final flush (its backpressure would block
    # forever on the dead drainer) but still try to land their trace; so do
    # workers released from an injected hang/stall — their in-flight rows
    # are the "lost on crash" loss the fault is simulating.
    if not orphaned and not hung:
        flush()
    if trace_dir:
        try:
            trace.export(
                os.path.join(trace_dir, f"trace_actor{worker_id}.json")
            )
        except Exception:
            pass  # diagnostics must never fail a clean worker exit


def _flush_truncated(nstep, bootstrap_obs):
    """Emit the pending partial windows of a TRUNCATED episode. Unlike the
    terminal flush inside NStepAccumulator.push, these keep a nonzero
    bootstrap discount (the episode didn't end — time just ran out)."""
    out = []
    for e, pend in enumerate(nstep._pending):
        while pend:
            o, a, r, disc, nobs = nstep._emit(
                pend, bootstrap_obs, terminal=False, length=len(pend)
            )
            out.append((o, a, r, disc, nobs))
            pend.popleft()
    return out
