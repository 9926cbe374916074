"""Stall watchdog: failure detection for the device-bound hot loop
(SURVEY.md §5 'Failure detection' row).

The actor side already has heartbeats + respawn (actors/pool.py) because
workers are stateless. The LEARNER side's failure mode is different: every
device interaction (`device_get`, dispatch, even PJRT client creation) is
a potentially-unbounded blocking call with no timeout parameter, so a
wedged device turns the trainer into a silent hang. A hang is the worst
outcome for a driver-managed run: a crash gets retried/diagnosed, a hang
eats the whole wall-clock budget.

`Watchdog` converts that hang into a loud, debuggable crash. When progress
stops advancing for `timeout_s` it:

  1. writes a STRUCTURED stall report (`stall_report.json`: every thread's
     stack as JSON, last progress value, seconds stalled) plus — when the
     flight recorder (trace.py) is enabled — `stall_trace.json`, the
     last-N-seconds cross-thread timeline, into `stall_dir`. Both writes
     are best-effort: a full disk must not mask the stall itself;
  2. dumps every thread's stack to stderr (faulthandler — shows exactly
     which device call wedged) and hard-exits via `os._exit` (the default
     `on_stall`). `os._exit` is deliberate: normal teardown would block on
     the same wedged device (pool.stop syncs, AsyncSaver waits), and
     atexit handlers of a wedged PJRT client can hang too.

Step 1 is what turns "exit 70 + a wall of stacks" into a diagnosable
artifact set: the trace answers what the shipper/prefetcher/eval threads
were doing in the seconds BEFORE the learner thread wedged, which the
stack dump (a single instant) cannot.

Enabled by `config.watchdog_s > 0` (train.py wires it around train_jax's
whole device lifetime, including learner construction and the first
params d2h — both observed wedge points).

Coverage note: this watchdog catches LEARNER-side wedges (device calls
that never return). Two adjacent failure modes are owned elsewhere and
exit differently (docs/RESILIENCE.md exit-code contract): a HOST-initiated
pod collective whose peer died is bounded by the pod collective deadline
(parallel/multihost.py PodPeerLost -> coordinated clean abort, exit 76) —
keep pod_collective_timeout_s well under watchdog_s so peer loss surfaces
as the resumable 76, with this watchdog's 70 as the backstop for
collectives INSIDE jitted dispatch, which no host-side deadline can
bound. An actor-side stall — workers heartbeating but
producing no experience — is invisible to it, because the warmup/cap
loops beat every iteration whether or not rows moved. That blind spot is
covered twice over: PER-WORKER by the pool monitor's zero-rows detector
(config.actor_no_progress_s — a worker that heartbeats but delivers no
rows past the threshold is respawned through the same backoff/quarantine
path as a dead one; actors/pool.py), and FLEET-WIDE by train.py's
secondary deadline (no ingest at all for 10x watchdog_s raises a loud
RuntimeError on the healthy learner thread). The first post-warmup
dispatch gets a one-time `grant()` so its XLA compile isn't killed as a
false stall."""

from __future__ import annotations

import faulthandler
import os
import sys
import threading
import time
from typing import Callable, Optional

from distributed_ddpg_tpu import trace

# EX_SOFTWARE: internal failure, distinguishable from OOM/kill. The code
# itself lives in the one-place exit contract (exits.py).
from distributed_ddpg_tpu.exits import EXIT_WATCHDOG_STALL as _EXIT_CODE

# stop() reap bound for the watchdog thread. The thread polls _stop every
# poll tick, so this only trips when the watchdog itself is wedged mid-
# artifact-write — and then the daemon flag reaps it at exit anyway.
_STOP_JOIN_S = 5.0


def _default_on_stall(timeout_s: float) -> None:
    sys.stderr.write(
        f"\n=== watchdog: no trainer progress for {timeout_s:.0f}s — "
        "dumping all thread stacks and aborting (a blocking device call "
        f"has likely wedged; exit code {_EXIT_CODE}) ===\n"
    )
    sys.stderr.flush()
    faulthandler.dump_traceback(all_threads=True)
    os._exit(_EXIT_CODE)


class Watchdog:
    """Fire `on_stall` if `progress()` stops changing for `timeout_s`.

    `progress` must be cheap, thread-safe, and must never touch the device
    (a device call inside the watchdog would wedge the watchdog with the
    thing it watches) — an int counter bumped by the supervised loop is the
    intended shape.

    `stall_dir`: where the structured stall artifacts land before
    `on_stall` runs (stall_report.json + stall_trace.json — see module
    docstring). None disables artifact writing (unit tests of the bare
    firing logic). `trace_window_s` bounds the exported timeline to the
    run-up to the stall.
    """

    def __init__(
        self,
        timeout_s: float,
        progress: Callable[[], object],
        on_stall: Optional[Callable[[], None]] = None,
        stall_dir: Optional[str] = None,
        trace_window_s: float = 30.0,
    ):
        if timeout_s <= 0:
            raise ValueError(f"timeout_s must be > 0, got {timeout_s}")
        self._timeout_s = timeout_s
        self._progress = progress
        self._on_stall = on_stall or (lambda: _default_on_stall(timeout_s))
        self._stall_dir = stall_dir
        self._trace_window_s = trace_window_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._grant_deadline = 0.0
        self._grant_lock = threading.Lock()
        # Paths written by the stall path; exposed so a custom on_stall
        # (tests, alternative supervisors) can pick the artifacts up.
        self.stall_artifacts: dict = {}

    def grant(self, extra_s: float) -> None:
        """Suppress firing until `extra_s` seconds from NOW (wall-clock
        deadline, not beat-relative): progress beats between grant() and the
        protected long call must not consume the allowance — the caller
        can't always avoid beating in between. Used for the first
        post-warmup learner dispatch, which includes the full XLA compile
        of the chunk program — worst-case compile (large nets, multihost
        meshes) can exceed a `timeout_s` tuned for steady-state dispatch
        latency, and a compile killed as a false stall exits 70 exactly
        like a real wedge."""
        with self._grant_lock:
            self._grant_deadline = max(
                self._grant_deadline, time.monotonic() + float(extra_s)
            )

    def start(self) -> "Watchdog":
        self._thread = threading.Thread(
            target=self._run, name="stall-watchdog", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=_STOP_JOIN_S)

    def _write_stall_artifacts(self, last_value, stalled_s: float) -> None:
        """Best-effort structured stall dump BEFORE on_stall (which, by
        default, os._exits). trace.stall_report never raises."""
        if self._stall_dir is None:
            return
        self.stall_artifacts = trace.stall_report(
            self._stall_dir,
            reason=(
                f"watchdog: no trainer progress for {self._timeout_s:.0f}s"
            ),
            timeout_s=self._timeout_s,
            window_s=self._trace_window_s,
            extra={
                "last_progress_value": repr(last_value),
                "stalled_s": round(stalled_s, 3),
            },
        )
        if self.stall_artifacts:
            sys.stderr.write(
                "watchdog: stall artifacts written: "
                + ", ".join(sorted(self.stall_artifacts.values()))
                + "\n"
            )
            sys.stderr.flush()

    def _run(self) -> None:
        last = self._progress()
        last_change = time.monotonic()
        # Poll well inside the timeout so a stall is detected within
        # ~1.25x timeout_s worst-case.
        poll = max(0.05, self._timeout_s / 4.0)
        while not self._stop.wait(poll):
            now_val = self._progress()
            now = time.monotonic()
            if now_val != last:
                last = now_val
                last_change = now
            elif now - last_change >= self._timeout_s:
                with self._grant_lock:
                    granted = now < self._grant_deadline
                if not granted:
                    # Telemetry plane first (obs/health.py): /healthz must
                    # read `draining` while the artifacts below are being
                    # written — the last scrape a supervisor gets from a
                    # wedged process should say "terminal", not "healthy".
                    # Latched, never raises; broad except because the
                    # stall path must not gain failure modes.
                    try:
                        from distributed_ddpg_tpu.obs import health

                        health.get().drain(
                            "watchdog stall: no trainer progress for "
                            f"{now - last_change:.0f}s"
                        )
                    except Exception:
                        pass
                    self._write_stall_artifacts(last, now - last_change)
                    self._on_stall()
                    return
