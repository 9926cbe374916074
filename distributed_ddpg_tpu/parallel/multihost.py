"""Multi-host (DCN) initialization + pod-resilience layer (SURVEY.md §7
step 6; BASELINE.json:11-12, the v5e-16 'cross-host AllReduce' rung;
docs/RESILIENCE.md pod rows).

The reference's cross-host story is distributed TF's gRPC parameter server
(SURVEY.md §2 #10). Here it is `jax.distributed.initialize`: after it runs,
`jax.devices()` spans all hosts, the SAME (data, model) mesh and learner jit
from parallel/ cover the pod, and XLA lowers the gradient AllReduce
hierarchically (ICI within a host, DCN across hosts). No framework code
changes between 1 host and N hosts — only this bootstrap.

Each host runs its own actors and replay shard and feeds its local devices.
Feeding works unchanged across processes: `jax.device_put` with a global
NamedSharding places each process's addressable shards (every process must
call it with the same global array — true here since learner inputs are
deterministic given the replay contents), and
`jax.make_array_from_process_local_data` remains the explicit per-host
alternative. Both paths (and full cross-process learner parity) are
exercised by tests/test_multihost.py over a 2-process Gloo CPU cluster.

Pod resilience (the PR-6 layer; docs/RESILIENCE.md):

Podracer-style deployments (PAPERS.md arXiv 2104.06272) run on preemptible
pods where single-process death is the COMMON failure — and a gloo/DCN
collective whose peer died blocks the survivors forever with no error.
This module therefore owns three defenses, all centralized at the single
audited entry point every host-initiated collective already goes through:

  1. **Collective deadlines.** `call_with_deadline` bounds any guarded
     collective by `pod_collective_timeout_s` (configure_pod; the transfer
     scheduler's lockstep lane wraps its beats through the same function).
     A hung collective surfaces as a typed `PodPeerLost` instead of an
     eternal block; single-process runs (deadline unconfigured) pay zero
     overhead — the wrapper short-circuits to a direct call. `grant()`
     extends the deadline across known-long windows (first-chunk XLA
     compile), mirroring the stall watchdog's grant.
  2. **Peer liveness.** `beat_allgather` piggybacks a heartbeat word (a
     per-process beat sequence number) on the existing sync_ship beat
     payload, so every successful beat refreshes a last-known-alive
     vector. When a collective dies, the PodPeerLost message carries that
     vector plus the peer id parsed (best-effort) from the transport
     error — survivors learn which process died within a bounded number
     of beats.
  3. **Coordinated resume.** `elect_resume_step` all-gathers each
     process's manifest-valid checkpoint steps and returns the greatest
     step present on EVERY process, so a pod restarting after a clean
     abort (train.py EXIT_POD_DEGRADED) never resumes forked.

`startup_barrier` is the one-time rendezvous with its own generous grace
(pod_startup_grace_s), distinct from the steady-state deadline: process
startup skew under box load (backend init, imports) must not eat into —
or false-fire — the much tighter collective deadline.
"""

from __future__ import annotations

import os
import re
import threading
import time
from typing import Iterable, Optional


class PodPeerLost(RuntimeError):
    """A pod-level host-initiated collective missed its deadline or failed
    mid-flight: some peer process is gone (crashed, preempted, or hung).
    Survivors must take the coordinated clean abort (train.py: drain the
    transfer scheduler, one emergency checkpoint, exit EXIT_POD_DEGRADED)
    — any further collective would block or fork the pod.

    `peer` is the lost process id when the transport error named one
    (best-effort; None for a silent timeout). `reason` is "timeout" or
    "error"."""

    def __init__(self, message: str, peer: Optional[int] = None,
                 reason: str = "timeout"):
        super().__init__(message)
        self.peer = peer
        self.reason = reason


# --- module pod state (configured once per train run by train_jax) --------
_pod_lock = threading.Lock()
_tls = threading.local()  # re-entrancy: nested guards must not double-arm
_pod_deadline_s = 0.0        # 0 = deadlines off (single-process default)
_pod_stats = None            # metrics.PodStats, when train.py wires one
_pod_grace_until = 0.0       # monotonic deadline extension (grant())
_beat_seq = 0                # this process's heartbeat word
_last_heartbeats = None      # last gathered per-process heartbeat vector


def configure_pod(timeout_s: float, stats=None) -> None:
    """Arm (or, with 0, disarm) the pod collective deadline and attach the
    PodStats sink. train_jax calls this only on multi-process runs, so
    single-process collectives keep the zero-overhead direct path."""
    global _pod_deadline_s, _pod_stats, _pod_grace_until, _beat_seq
    global _last_heartbeats
    with _pod_lock:
        _pod_deadline_s = max(0.0, float(timeout_s))
        _pod_stats = stats
        _pod_grace_until = 0.0
        if _pod_deadline_s == 0.0:
            _beat_seq = 0
            _last_heartbeats = None


def grant(extra_s: float) -> None:
    """Suppress deadline firing until `extra_s` seconds from NOW — the pod
    sibling of Watchdog.grant, for known-long lockstep windows (the first
    chunk dispatch's XLA compile can skew processes by more than the
    steady-state deadline; a compile-skewed peer is not a dead peer)."""
    global _pod_grace_until
    with _pod_lock:
        _pod_grace_until = max(
            _pod_grace_until, time.monotonic() + float(extra_s)
        )


def pod_deadline_s() -> float:
    """The currently-armed steady-state deadline (0 = off)."""
    return _pod_deadline_s


def beat_result_timeout_s(default_s: float = 600.0) -> float:
    """Outer wait bound for a background lockstep/shard_exchange beat
    ticket (replay/device.py sync_ship, train.py wait_beat). With the pod
    deadline armed, the lane's in-flight beat is already bounded by
    call_with_deadline — so the ticket wait only needs to cover at most
    one queued beat behind one in-flight beat, plus any active grant
    window (first-chunk compile) and dispatch slack. A wedge therefore
    surfaces as a typed failure within a small multiple of
    pod_collective_timeout_s instead of a hardcoded 10-minute stall;
    deadline unconfigured (single-process, or 0 = off) keeps the generous
    `default_s` — there is no peer to lose, only teardown stragglers."""
    t = _pod_deadline_s
    if t <= 0:
        return float(default_s)
    with _pod_lock:
        grace = max(0.0, _pod_grace_until - time.monotonic())
    return 2.0 * t + grace + 30.0


def wait_beat_ticket(ticket, label: str = "sync_ship beat"):
    """Resolve one background ordered-lane beat ticket under the derived
    deadline (beat_result_timeout_s), converting a TimeoutError into
    typed PodPeerLost — the ONE owner of the bounded-wait contract for
    both waiters (replay/device.py sync_ship's synchronous facade and
    train.py's wait_beat gate), so the timeout policy and the typed-abort
    message can never drift between them. Returns the beat's result;
    re-raises the beat's own exception (e.g. the lane deadline's
    PodPeerLost) unchanged."""
    timeout = beat_result_timeout_s()
    try:
        return ticket.result(timeout=timeout)
    except TimeoutError as e:
        _note_peer_lost(f"pod_peer_lost:{label}")
        raise PodPeerLost(
            f"background {label} unresolved after {timeout:.0f}s — the "
            "ordered beat lane is wedged (scheduler stalled or a peer "
            "process is gone)",
            reason="timeout",
        ) from e


def call_with_deadline(fn, timeout_s: Optional[float] = None,
                       label: str = "collective"):
    """Run `fn` bounded by the pod collective deadline. timeout_s=None
    uses the configured default; <= 0 (or an unconfigured default)
    SHORT-CIRCUITS to a direct call on the caller's thread — the
    single-process zero-overhead contract tests pin.

    A guarded call runs on a helper thread; if the deadline (plus any
    active grant) passes first, a `PodPeerLost(reason="timeout")` raises
    on the caller while the abandoned helper blocks on — the caller is
    aborting the process anyway, and a wedged gloo/DCN op has no cancel
    API. Successful calls record their elapsed time into PodStats (the
    collective_timeout near-miss / slack telemetry)."""
    t = _pod_deadline_s if timeout_s is None else float(timeout_s)
    if t <= 0 or getattr(_tls, "guarded", False):
        # Off, or already running under an outer guard (the scheduler's
        # lockstep wrap around a beat whose inner allgather is guarded
        # too): one deadline per collective, one helper thread, one
        # peer-lost count.
        return fn()
    with _pod_lock:
        grace_left = _pod_grace_until - time.monotonic()
    if grace_left > 0:
        # The grant EXTENDS the deadline by the remaining grace (the
        # documented worst-case detection latency is timeout + grace).
        t += grace_left
    box: dict = {}
    done = threading.Event()

    def _run():
        _tls.guarded = True
        try:
            box["result"] = fn()
        except BaseException as e:  # delivered to the waiting caller
            box["exc"] = e
        finally:
            done.set()

    t0 = time.monotonic()
    helper = threading.Thread(
        target=_run, daemon=True, name=f"pod-deadline-{label}"
    )
    helper.start()
    if not done.wait(t):
        stats = _pod_stats
        if stats is not None:
            stats.record_peer_lost()
        from distributed_ddpg_tpu import trace

        trace.instant("pod_peer_lost", label=label, deadline_s=t)
        _note_peer_lost(f"pod_peer_lost:{label}")
        raise PodPeerLost(
            f"pod collective {label!r} missed its {t:.1f}s deadline — a "
            f"peer process is gone or hung ({_liveness_note()})",
            reason="timeout",
        )
    elapsed = time.monotonic() - t0
    if "exc" in box:
        raise box["exc"]
    # Success only: failed collectives must not steer the near-miss /
    # slack telemetry the deadline is tuned from.
    stats = _pod_stats
    if stats is not None:
        stats.record_collective(elapsed, t)
    return box["result"]


def _note_peer_lost(reason: str) -> None:
    """Flip the process health state (obs/health.py) to degraded the
    moment a peer is declared lost — the /healthz endpoint must read
    degraded DURING the coordinated abort's teardown window (emergency
    checkpoint, election, linger), not only in the exit code after it.
    Lazy import + broad except: the typed-abort path must never gain a
    new failure mode from a diagnostics layer."""
    try:
        from distributed_ddpg_tpu.obs import health

        health.get().note(reason)
    except Exception:
        pass


def _parse_peer(message: str) -> Optional[int]:
    """Best-effort peer id from a transport/coordination error message
    (jax's coordination service and gloo both name the failed task/rank
    in most death reports)."""
    m = re.search(r"(?:task|process|peer|rank)[\s:#=]*(\d+)",
                  message, re.IGNORECASE)
    return int(m.group(1)) if m else None


def _liveness_note() -> str:
    """One-line last-known-alive summary for PodPeerLost messages: the
    heartbeat vector from the most recent successful beat."""
    with _pod_lock:
        beats = _last_heartbeats
        seq = _beat_seq
    if beats is None:
        return "no heartbeat beat completed yet"
    return (
        f"last heartbeats per process {list(int(b) for b in beats)} "
        f"at local beat {seq}"
    )


def last_heartbeats():
    """The most recent gathered per-process heartbeat vector (or None)."""
    with _pod_lock:
        return None if _last_heartbeats is None else _last_heartbeats.copy()


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    runtime_heartbeat_timeout_s: Optional[float] = None,
) -> bool:
    """Idempotent jax.distributed bootstrap. Args fall back to the standard
    env vars (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID,
    or cloud-TPU auto-detection when none are set). Returns True if a
    multi-process runtime was initialized, False for single-process runs.

    `runtime_heartbeat_timeout_s` stretches the JAX runtime's OWN death
    detection (see the comment at the call below); train_jax derives it
    from the pod deadline + grace so the clean-abort contract holds by
    default, and the POD_RUNTIME_HEARTBEAT_TIMEOUT_S env var remains the
    operator override."""
    import jax

    coordinator_address = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])

    if coordinator_address is None and num_processes is None:
        # Single process (or cloud-TPU metadata auto-detect, which
        # jax.distributed.initialize() handles with no args — only attempt it
        # when a TPU runtime is actually present).
        return False

    try:
        # Stretch the JAX coordination service's OWN death detection
        # (default heartbeat_timeout_seconds=100, after which the C++
        # client LOG(FATAL)s the process — a SIGABRT with no emergency
        # checkpoint). The pod layer's collective deadline must WIN that
        # race so survivors abort cleanly with exit 76: train_jax passes
        # a value derived from pod_collective_timeout_s +
        # pod_startup_grace_s; POD_RUNTIME_HEARTBEAT_TIMEOUT_S overrides.
        hb_env = os.environ.get("POD_RUNTIME_HEARTBEAT_TIMEOUT_S")
        hb = float(hb_env) if hb_env else runtime_heartbeat_timeout_s
        stretch = (
            {"heartbeat_timeout_seconds": max(1, int(round(hb)))}
            if hb and hb > 0
            else {}
        )
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            **stretch,
        )
        return jax.process_count() > 1
    except RuntimeError as e:
        msg = str(e)
        # Idempotent re-entry ("...should only be called once").
        if "only be called once" in msg:
            return jax.process_count() > 1
        if "must be called before" in msg and jax.process_count() > 1:
            # Backend already live AND already multi-process: a legitimate
            # idempotent re-entry (the application bootstrapped distributed
            # before calling train). If the live backend is single-process,
            # the explicit multi-host request genuinely failed — re-raise
            # rather than silently training N independent copies.
            return True
        raise


# Every integer pod-layer gather (startup barrier, sync_ship beats, the
# env-budget gather, the resume election) is padded into one int64 vector
# of this many slots, so they ALL reuse a single compiled all-gather
# executable. One executable means one wire size for every host gather:
# even if the gloo CPU transport interleaves streams (its collective ops
# carry no type tag, only byte counts), the pod layer can never feed it
# mismatched op sizes. The election's newest-8-steps window is sized to
# this.
_UNIFORM_SLOTS = 8


def allgather_scalar(value, dtype=None, timeout_s: Optional[float] = None,
                     label: str = "allgather"):
    """All-gather one host scalar (or small fixed-shape vector) across
    processes; returns a numpy array of shape [process_count, ...]. The
    ONE host-initiated DCN collective the ingest/budget machinery needs
    (replay/device.py sync_ship beats, train.py's global env-step budget).
    Centralized here so every caller — including the transfer scheduler's
    lockstep lane, which must be the only thread issuing host-initiated
    collectives when background sync_ship is active (docs/TRANSFER.md) —
    goes through one audited, DEADLINE-GUARDED entry point: a hung gather
    raises PodPeerLost at the configured pod_collective_timeout_s instead
    of blocking forever, and a transport error on a multi-process run is
    typed the same way (a failed pod collective means a peer is gone —
    the pod must abort cleanly either way). Small integer payloads ride
    the uniform int64[_UNIFORM_SLOTS] transport (see above)."""
    import numpy as np

    arr = np.asarray(value, dtype) if dtype is not None else np.asarray(value)
    uniform = arr.dtype.kind in "iu" and arr.ndim <= 1 and arr.size <= _UNIFORM_SLOTS

    def _gather():
        from jax.experimental import multihost_utils

        if uniform:
            payload = np.zeros((_UNIFORM_SLOTS,), np.int64)
            payload[: arr.size] = arr.reshape(-1)
            out = np.asarray(multihost_utils.process_allgather(payload))
            out = out[:, : arr.size] if arr.ndim else out[:, 0]
            return out.astype(arr.dtype, copy=False)
        return np.asarray(multihost_utils.process_allgather(arr))

    try:
        return call_with_deadline(_gather, timeout_s=timeout_s, label=label)
    except PodPeerLost:
        raise
    except Exception as e:
        import jax

        if jax.process_count() > 1:
            stats = _pod_stats
            if stats is not None:
                stats.record_peer_lost()
            from distributed_ddpg_tpu import trace

            trace.instant("pod_peer_lost", label=label, error=repr(e)[:120])
            _note_peer_lost(f"pod_peer_lost:{label}")
            raise PodPeerLost(
                f"pod collective {label!r} failed mid-flight: {e!r} "
                f"({_liveness_note()})",
                peer=_parse_peer(str(e)),
                reason="error",
            ) from e
        raise


def beat_allgather(count, label: str = "sync_ship_beat"):
    """All-gather one int payload per process with a piggybacked heartbeat
    word (this process's beat sequence number) — the sync_ship beat path
    (replay/device.py). Every successful beat refreshes the last-known-
    alive vector `last_heartbeats()`, so when a later collective dies the
    PodPeerLost message reports how recently each peer was provably alive
    (bounded by the beat cadence: one per learner chunk in train_jax).
    Returns the gathered payload column, shape [process_count]."""
    import numpy as np

    global _beat_seq, _last_heartbeats
    with _pod_lock:
        _beat_seq += 1
        seq = _beat_seq
    gathered = allgather_scalar(
        np.asarray([int(count), seq], np.int64), label=label
    )
    with _pod_lock:
        _last_heartbeats = gathered[:, 1].copy()
    stats = _pod_stats
    if stats is not None:
        stats.note_beat()
    return gathered[:, 0]


def startup_barrier(grace_s: float, label: str = "pod_startup_barrier") -> None:
    """One-time pod rendezvous with its own GENEROUS grace, distinct from
    the steady-state collective deadline: under box load a peer's backend
    init / imports can lag by tens of seconds (the documented gloo child
    startup flake, CHANGES.md PR 5), and that skew must be absorbed once
    here — not false-fire the much tighter per-beat deadline, and not
    surface as a mid-test heartbeat timeout. No-op single-process."""
    import jax

    if jax.process_count() <= 1:
        return
    import sys

    import numpy as np

    t0 = time.monotonic()
    allgather_scalar(
        np.int32(jax.process_index()), timeout_s=float(grace_s), label=label
    )
    print(
        f"[pod] startup barrier: {jax.process_count()} processes "
        f"synchronized in {time.monotonic() - t0:.1f}s",
        file=sys.stderr, flush=True,
    )


def clock_handshake(label: str = "clock_handshake") -> Optional[dict]:
    """Startup monotonic<->wall offset handshake (docs/OBSERVABILITY.md
    §4): each process all-gathers its wall clock (int64 ms — the uniform
    transport, one more reuse of the single compiled gather executable)
    at ONE synchronized point, so every host learns every other host's
    wall-clock offset relative to rank 0. The per-host flight-recorder
    ring anchors timestamps to its own (wall_t0, perf_counter) pair;
    these offsets are the correction term `tools.runs merge-trace` uses
    to put N per-host timelines on one aligned clock — without them a
    skewed NTP host's spans land visibly out of order against the
    collectives they participated in. The gather itself bounds the skew
    measurement error at the collective's in-flight time. Returns
    {"wall_ms": [per-host], "offset_ms": [per-host, rank0-relative]};
    None single-process."""
    import jax
    import numpy as np

    if jax.process_count() <= 1:
        return None
    gathered = allgather_scalar(
        np.int64(int(time.time() * 1000.0)), label=label
    )
    wall_ms = [int(v) for v in np.asarray(gathered).reshape(-1)]
    return {
        "wall_ms": wall_ms,
        "offset_ms": [v - wall_ms[0] for v in wall_ms],
    }


def _common_step(gathered) -> int:
    """The greatest checkpoint step present on EVERY process, from the
    [process_count, k] gathered step matrix (-1 entries = padding). -1
    when no step is common. Pure so the election rule is unit-testable
    without a cluster; every process computes it from the identical
    gathered matrix, so the pod can never disagree."""
    import numpy as np

    rows = np.asarray(gathered, np.int64)
    common = None
    for row in rows:
        steps = {int(v) for v in row if int(v) >= 0}
        common = steps if common is None else (common & steps)
    return max(common) if common else -1


def elect_resume_step(local_steps: Iterable[int], limit: int = 8) -> int:
    """Coordinated resume election (docs/RESILIENCE.md): all-gather each
    process's newest `limit` manifest-valid checkpoint steps and return
    the greatest step available on EVERY process — restoring anything
    newer on some processes only would fork the pod. -1 = no common step
    (every process then starts fresh, which is also agreed). ALL
    processes must call this at the same point (train_jax resume)."""
    import numpy as np

    steps = sorted({int(s) for s in local_steps})[-max(1, int(limit)):]
    vec = np.full((max(1, int(limit)),), -1, np.int64)
    if steps:
        vec[: len(steps)] = np.asarray(steps, np.int64)
    gathered = allgather_scalar(vec, label="resume_step_election")
    return _common_step(gathered)


def elect_slice_step(local_step: Optional[int]) -> int:
    """Coordinated replay-slice adoption election (elastic pod;
    docs/REPLAY_SHARDING.md all-writer checkpoints): all-gather each
    process's newest complete slice step
    (checkpoint.latest_complete_slice_step) and adopt it only when EVERY
    process sees the SAME step — on a shared checkpoint filesystem that
    is the common case; under NFS visibility skew or per-host disks a
    disagreement must resolve to 'nobody adopts' (-1, every buffer
    resumes empty — also agreed), because a pod where some processes
    load rows and others don't has forked its data distribution. Rides
    the uniform int64 transport like every pod gather; single-process
    returns the local answer directly. ALL processes must call this at
    the same point (train_jax resume, right after the step election)."""
    import jax
    import numpy as np

    local = -1 if local_step is None else int(local_step)
    if jax.process_count() <= 1:
        return local
    gathered = allgather_scalar(
        np.int64(local), label="slice_step_election"
    )
    vals = {int(v) for v in np.asarray(gathered).reshape(-1)}
    return local if vals == {local} and local >= 0 else -1


def process_info() -> dict:
    import jax

    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_device_count": jax.local_device_count(),
        "global_device_count": jax.device_count(),
    }
