"""Double-buffered host->HBM minibatch pipeline (SURVEY.md §7 step 5 and
'hard parts (a)': a >=20x-faster learner starves unless sampling + h2d leave
the step's critical path).

A daemon thread samples K minibatches from replay, stacks them into one
[K, B, ...] super-batch, and `jax.device_put`s it with the chunk sharding
(device_put is async — the transfer overlaps the learner's current chunk).
`depth` bounds the queue: depth=2 is classic double buffering (one chunk in
compute, one in flight). Sample indices stay host-side and ride along for
PER priority updates after the chunk's TD errors come back.
"""

from __future__ import annotations

import queue
import threading
import time
import warnings
from typing import Dict, Optional

import numpy as np

from distributed_ddpg_tpu import trace


# stop()-path drain bound: how long the worker grants an in-flight
# transfer ticket to land after stop is requested, before abandoning it
# to the scheduler (whose close() fails pending tickets loudly). A bound
# on shutdown courtesy, not a liveness deadline — liveness is next()'s
# PrefetchTimeout.
_STOP_DRAIN_S = 5.0


class PrefetchError(RuntimeError):
    """The prefetch worker thread died; the original exception rides along
    as __cause__ (the IngestError surfacing discipline). Subclasses
    RuntimeError so pre-existing blanket handlers keep working."""


class PrefetchTimeout(RuntimeError):
    """next() deadline expired with the worker thread still alive — replay
    starvation or a wedged device transfer, NOT a worker crash (a dead
    worker surfaces as 'prefetch thread died' with its real exception
    chained). Named so callers can distinguish a stall from the bare
    queue.Empty internals."""


class ChunkPrefetcher:
    def __init__(
        self,
        replay,
        put_chunk,                  # ShardedLearner.put_chunk (or any device placer)
        batch_size: int,
        chunk_size: int,
        depth: int = 2,
        lock: Optional[threading.Lock] = None,
        fault=None,                 # faults.FaultSite ticked per sample
        scheduler=None,             # transfer.TransferScheduler (optional)
    ):
        self._replay = replay
        self._put = put_chunk
        # Unified transfer scheduler (docs/TRANSFER.md): when attached,
        # the h2d device_put is submitted as a 'prefetch'-class work item
        # instead of running inline — the scheduler's fair queue then
        # rate-balances it against replay-ingest super-blocks (neither
        # stream can starve the other). Sampling stays on this worker
        # thread: it is CPU work, not bus work.
        self._sched = scheduler
        self._batch_size = batch_size
        self._chunk = chunk_size
        self._lock = lock or threading.Lock()
        # Chaos harness (faults.py): prefetch:sample:hang@k~s sleeps the
        # k-th chunk sample (PrefetchTimeout territory when s exceeds
        # next()'s deadline); prefetch:sample:crash@k kills the worker
        # thread, surfacing via next()'s 'prefetch thread died'.
        self._fault = fault
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._exc: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, daemon=True, name="prefetch")

    def start(self) -> "ChunkPrefetcher":
        self._thread.start()
        return self

    def _sample_chunk(self) -> Dict[str, np.ndarray]:
        # Flight-recorder span: host-replay sampling time on the prefetch
        # thread — when the learner's sample_wait phase grows, the
        # timeline shows whether THIS (lock contention, sample cost) or
        # the h2d below is the bottleneck.
        if self._fault is not None:
            self._fault.tick()
        with trace.span("prefetch_sample"):
            samples = []
            with self._lock:
                for _ in range(self._chunk):
                    samples.append(self._replay.sample(self._batch_size))
            return {
                k: np.stack([s[k] for s in samples]) for k in samples[0]
            }

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                chunk = self._sample_chunk()
                indices = chunk.pop("indices")
                # Re-check stop BEFORE committing to the device transfer:
                # put_chunk blocks on h2d (unboundedly, on a wedged
                # device), and a stop() issued while we sampled must not
                # strand the join behind a transfer nobody will consume.
                if self._stop.is_set():
                    return
                if self._sched is not None:
                    nbytes = sum(
                        getattr(v, "nbytes", 0) for v in chunk.values()
                    )
                    ticket = self._sched.submit(
                        "prefetch", lambda: self._put(chunk),
                        nbytes=nbytes, label="prefetch_h2d",
                    )
                    # Bounded waits so a stop() during a scheduler stall
                    # still joins; a dead scheduler surfaces through the
                    # ticket as TransferError -> next()'s 'prefetch
                    # thread died'.
                    while not ticket.done():
                        if self._stop.is_set():
                            ticket.wait(_STOP_DRAIN_S)
                            break
                        ticket.wait(0.1)
                    if not ticket.done():
                        return
                    device_chunk = ticket.result(timeout=0.0)
                else:
                    with trace.span("prefetch_h2d"):
                        device_chunk = self._put(chunk)
                # Block here (not in get()) when the queue is full — this is
                # the backpressure that makes `depth` the buffer bound.
                while not self._stop.is_set():
                    try:
                        self._q.put((device_chunk, indices), timeout=0.1)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # surface in next()
            self._exc = e

    def next(self, timeout: float = 60.0):
        """Returns (device_chunk, host_indices[K, B]). Re-checks for a dead
        worker while waiting so its real exception surfaces promptly instead
        of an unrelated queue timeout; a deadline with the worker ALIVE
        raises PrefetchTimeout (named), never a bare queue.Empty."""
        deadline = time.monotonic() + timeout
        while True:
            if self._exc is not None:
                raise PrefetchError("prefetch thread died") from self._exc
            try:
                return self._q.get(timeout=min(0.5, max(0.0, deadline - time.monotonic())))
            except queue.Empty:
                if time.monotonic() >= deadline:
                    raise PrefetchTimeout(
                        f"no prefetched chunk within {timeout:.1f}s with the "
                        "worker alive — replay starvation or a wedged "
                        "device transfer"
                    ) from None

    def stop(self, timeout: float = 5.0) -> bool:
        """Stop the worker and join it. Drains the queue REPEATEDLY while
        joining: a worker blocked in q.put refills the single slot a
        one-shot drain frees, and a worker blocked inside put_chunk's
        device transfer may surface one more chunk before seeing the stop
        flag. Returns False (with a warning) if the worker is still alive
        at the deadline — it can only be wedged inside an uninterruptible
        device transfer; the daemon thread is leaked rather than hanging
        teardown forever."""
        self._stop.set()
        deadline = time.monotonic() + timeout
        while self._thread.is_alive() and time.monotonic() < deadline:
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.1)
        if self._thread.is_alive():
            warnings.warn(
                "prefetch worker did not exit within "
                f"{timeout:.1f}s (blocked in a device transfer?); leaking "
                "the daemon thread"
            )
            return False
        return True
