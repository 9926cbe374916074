"""Structured metrics: JSONL + stdout (SURVEY.md §5 'Metrics / logging').

The reference's only observability was TensorBoard scalar summaries
[RECALL]; here the sink is append-only JSONL (one object per event,
machine-parseable by the benchmark's harness) plus optional human lines.
Tracked quantities follow SURVEY.md §5: episode return, losses, mean Q,
grad norms, buffer fill, actor/learner steps/sec, staleness.
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time
import zlib
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

from distributed_ddpg_tpu import trace


class MetricsLogger:
    def __init__(
        self,
        path: str = "",
        echo: bool = True,
        header: Optional[Dict[str, Any]] = None,
    ):
        self._file = open(path, "a", buffering=1) if path else None
        self._echo = echo
        self._t0 = time.time()
        # log() is called from the train loop AND from the background eval
        # thread (train.py); serialize sinks so JSONL lines never interleave.
        self._lock = threading.Lock()
        # Latest record per kind: the live /metrics endpoint's source
        # (obs/exporter.py) — a scrape must never replay the file.
        self._latest: Dict[str, Dict[str, Any]] = {}
        # Every stream opens with ONE header record carrying the absolute
        # wall-clock base: `wall_time` below is seconds since logger
        # creation, so without this a pod's N per-process JSONL files (or
        # two runs of one config) cannot be joined on time at all —
        # merge tooling computes absolute event time as
        # t_unix_base + wall_time (docs/OBSERVABILITY.md §1). `header`
        # adds the caller's run facts (train_jax: device + learner leg).
        self.t_unix_base = round(self._t0, 6)
        self.log(
            "header", 0, t_unix_base=self.t_unix_base, pid=os.getpid(),
            **(header or {}),
        )

    def log(self, kind: str, step: int, **fields: Any) -> Dict[str, Any]:
        rec = {
            "kind": kind,
            "step": step,
            "wall_time": round(time.time() - self._t0, 3),
            **{k: _jsonable(v) for k, v in fields.items()},
        }
        line = json.dumps(rec)
        with self._lock:
            self._latest[kind] = rec
            if self._file:
                self._file.write(line + "\n")
            if self._echo:
                print(line, file=sys.stdout, flush=True)
        return rec

    def latest(self) -> Dict[str, Dict[str, Any]]:
        """{kind: most recent record} — the /metrics render source
        (obs/exporter.py). Shallow-copied so the scrape thread iterates
        a stable dict while the train loop keeps logging."""
        with self._lock:
            return dict(self._latest)

    def close(self) -> None:
        if self._file:
            self._file.close()


def _jsonable(v):
    """JSONL field coercion. Bools and ints pass through AS THEIR TYPE —
    the old blanket float() turned `fused_chunk_active: true` into `1.0`
    in every record, which downstream parsers (tools/runs.py) then can't
    distinguish from a measured scalar. Floats (incl. numpy scalars) keep
    the 6-decimal rounding that bounds record size."""
    if isinstance(v, bool):
        return v
    if isinstance(v, int):
        return v
    if hasattr(v, "item") and not hasattr(v, "__len__"):
        # numpy/JAX zero-dim scalar: unwrap to the native type first so
        # np.bool_/np.int64 survive as bool/int.
        try:
            return _jsonable(v.item())
        except (TypeError, ValueError):
            return v
    try:
        return round(float(v), 6)
    except (TypeError, ValueError):
        return v


class _Reservoir:
    """Fixed-size uniform sample of per-call durations (Vitter's
    Algorithm R) + exact running max: the memory-bounded way to carry tail
    latencies (p50/p95) across an arbitrary-length logging interval.
    Deterministically seeded so strict_sync's bit-identical-metrics
    contract survives — two identical runs admit identical samples."""

    __slots__ = ("k", "n", "buf", "max", "_rng")

    def __init__(self, k: int, seed: int):
        self.k = k
        self.n = 0
        self.buf: List[float] = []
        self.max = 0.0
        self._rng = random.Random(seed)

    def add(self, x: float) -> None:
        self.n += 1
        if x > self.max:
            self.max = x
        if len(self.buf) < self.k:
            self.buf.append(x)
        else:
            j = self._rng.randrange(self.n)
            if j < self.k:
                self.buf[j] = x

    def percentile(self, q: float) -> float:
        """Nearest-rank percentile over the reservoir (q in [0, 1])."""
        s = sorted(self.buf)
        if not s:
            return 0.0
        return s[min(len(s) - 1, int(q * len(s)))]


class PhaseTimers:
    """Per-phase wall-time counters + tail latencies (SURVEY.md §5
    'per-step timing of sample→h2d→step→d2h'; VERDICT.md round-1 Weak #9).
    Phases are whatever the caller brackets — train_jax uses dispatch
    (chunk submit), ingest (actor h2d), sync (metrics d2h), sample_wait
    (host-prefetch starvation), ckpt, eval_snapshot. snapshot() emits per
    interval and resets:

      t_<name>_ms    mean ms per call (the seed's field — kept)
      n_<name>       calls in the interval
      t_<name>_p50 / t_<name>_p95 / t_<name>_max
                     reservoir percentiles + exact max, ms

    The percentiles are the point: the round-5 8-device ingest
    regression hid behind a healthy MEAN — a per-interval p95/max puts a
    one-in-fifty 600ms dispatch straight into the JSONL record instead of
    averaging it into noise. Every phase bracket also emits a flight-
    recorder span (trace.py) under the phase's name, so the same bracket
    feeds the scalar record, the Perfetto timeline and — through
    trace.py's annotator sink — the profiler's host plane. `**args` are
    the span's cause (chunk index, learner step): they go to the sinks,
    not to the record."""

    # Reservoir size: 256 doubles/phase bounds memory; p95 over a typical
    # 50-call interval is exact (reservoir bigger than the population).
    RESERVOIR_K = 256

    def __init__(self, seed: int = 0):
        self._acc: Dict[str, float] = {}
        self._n: Dict[str, int] = {}
        self._res: Dict[str, _Reservoir] = {}
        self._seed = seed

    @contextmanager
    def phase(self, name: str, **args):
        t0 = time.perf_counter()
        with trace.span(name, **args):
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                self._acc[name] = self._acc.get(name, 0.0) + dt
                self._n[name] = self._n.get(name, 0) + 1
                r = self._res.get(name)
                if r is None:
                    # Phase-name-derived seed: deterministic per phase
                    # AND per process — crc32, not hash(), because str
                    # hashing is salted per interpreter (PYTHONHASHSEED)
                    # and a run-varying seed would make which samples
                    # survive the reservoir (hence reported p50/p95)
                    # partly run-to-run noise.
                    r = self._res[name] = _Reservoir(
                        self.RESERVOIR_K,
                        (zlib.crc32(name.encode()) ^ self._seed) & 0x7FFFFFFF,
                    )
                r.add(dt)

    def snapshot(self, reset: bool = True) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for name, total in self._acc.items():
            n = max(self._n.get(name, 1), 1)
            out[f"t_{name}_ms"] = round(1000.0 * total / n, 3)
            out[f"n_{name}"] = self._n.get(name, 0)
            r = self._res.get(name)
            if r is not None and r.buf:
                out[f"t_{name}_p50"] = round(1000.0 * r.percentile(0.50), 3)
                out[f"t_{name}_p95"] = round(1000.0 * r.percentile(0.95), 3)
                out[f"t_{name}_max"] = round(1000.0 * r.max, 3)
        if reset:
            self._acc.clear()
            self._n.clear()
            self._res.clear()
        return out


class LaunchQueue:
    """Launches the device has not finished, counted from the host with
    no sync and no d2h: the learner loop keeps one small output leaf of
    every dispatched chunk, and before each dispatch drops the finished
    ones (`is_ready()`) from the old end. What is left is what a
    parameter refresh has to wait out and what policy lag is made of;
    a dispatch that finds nothing left found the device idle.

      poll()          before a dispatch: settle(), and note a starved
                      dispatch; returns the updates that finished
      add(leaf, n)    after it: the launch's leaf and its n updates
      drain()         before a read-back: wait out every queued launch,
                      one `launch_wait` span each, then settle()
      steps_done      updates of finished launches since the run began
                      (dispatched - in flight)

    snapshot() emits per interval and resets: launches_in_flight_mean /
    _max (depth seen by each dispatch, itself included) and
    n_dispatch_starved. Learner thread only: no lock."""

    def __init__(self):
        self._q = deque()  # (leaf, updates), oldest launch first
        self.n_dispatched = 0
        self.steps_done = 0
        self._reset()

    def _reset(self) -> None:
        self._n = self._sum = self._max = self._starved = 0

    def __len__(self) -> int:
        return len(self._q)

    def settle(self) -> int:
        """Drop the finished launches from the old end; returns the
        updates they held. Also called outside a dispatch, before a
        record or the final rate is written."""
        q, done = self._q, 0
        while q and q[0][0].is_ready():
            done += q.popleft()[1]
        self.steps_done += done
        return done

    def poll(self) -> int:
        done = self.settle()
        if not self._q and self.n_dispatched:
            self._starved += 1
        return done

    def drain(self) -> int:
        """Wait for every queued launch, oldest first, each under its own
        `launch_wait` span, then settle(). The span carries the index the
        launch's `dispatch` span carried, so on the profiler's host plane
        the k-th `launch_wait` ends when the host learns that the k-th
        launch on the device plane has ended (about 2 ms behind it on a
        v5e: PERF.md §5): no wait for the device is longer than one
        launch, and the instant the host finds the queue dry is a span
        boundary. Every read-back of the learner thread calls this first
        (train.py's `read_back`)."""
        first = self.n_dispatched - len(self._q)
        for k, (leaf, _) in enumerate(self._q):
            with trace.span("launch_wait", chunk=first + k):
                leaf.block_until_ready()
        return self.settle()

    def add(self, leaf, updates: int) -> None:
        self._q.append((leaf, updates))
        self.n_dispatched += 1
        depth = len(self._q)
        self._n += 1
        self._sum += depth
        if depth > self._max:
            self._max = depth

    def snapshot(self) -> Dict[str, float]:
        out = {
            "launches_in_flight_mean": (
                round(self._sum / self._n, 3) if self._n else 0.0
            ),
            "launches_in_flight_max": self._max,
            "n_dispatch_starved": self._starved,
        }
        self._reset()
        return out


class SetupStages:
    """The stages a job waits through before its first useful step, as
    disjoint spans on the thread that calls `stage()`: each stage ends
    where the next begins. Every stage is a `trace.span` (ring and
    profiler, where installed) and is timed on the monotonic clock into
    `spans` {name: seconds}; a name staged twice accumulates."""

    def __init__(self):
        self.spans: Dict[str, float] = {}
        self._open = None  # (name, span context, start_s)

    def stage(self, name: str) -> None:
        """End the open stage and begin `name`."""
        self.end()
        span = trace.span(name)
        span.__enter__()
        self._open = (name, span, time.perf_counter())

    def end(self) -> None:
        if self._open is None:
            return
        name, span, t0 = self._open
        self._open = None
        t1 = time.perf_counter()
        span.__exit__(None, None, None)
        self.add(name, t1 - t0)

    def add(self, name: str, seconds: float) -> None:
        """A stretch measured before anything could bracket it (the
        module's own import, the backend start in train())."""
        self.spans[name] = self.spans.get(name, 0.0) + seconds


class CompileCounter:
    """Programs this process compiled — builds that were NOT loads from
    the persistent cache — and their backend-compile seconds, from
    `jax.monitoring` between install() and uninstall(). JAX reports a
    cache hit as its own event right before the build's duration event,
    on the compiling thread; a build with no hit before it compiled."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self._hit = threading.local()
        self._lock = threading.Lock()
        self._installed = False

    def install(self) -> "CompileCounter":
        import jax.monitoring as m

        m.register_event_listener(self._on_event)
        m.register_event_duration_secs_listener(self._on_duration)
        self._installed = True
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        import jax.monitoring as m

        self._installed = False
        m.unregister_event_listener(self._on_event)
        m.unregister_event_duration_listener(self._on_duration)

    def _on_event(self, event, **kw) -> None:
        if event == self.HIT:
            self._hit.pending = True

    def _on_duration(self, event, seconds, **kw) -> None:
        if event != self.BUILD:
            return
        if getattr(self._hit, "pending", False):
            self._hit.pending = False
            return
        with self._lock:
            self.seconds += seconds
            self.programs += 1


class IngestStats:
    """Thread-safe counters for the replay ingest pipeline (docs/INGEST.md;
    the inbound mirror of PhaseTimers' outbound sample/h2d breakdown).

    Producers call record_push (rows staged + time spent stalled on a full
    staging ring); the shipper calls record_ship (rows/blocks moved to HBM
    per device call + the dispatch wall time). snapshot() emits the
    `ingest_*` fields each train record carries and resets the
    interval, so every JSONL line describes its own window:

      ingest_rows_per_sec   rows landed in HBM over the interval
      ingest_rows_staged    rows pushed into the staging ring over the
                            interval (staged - shipped trending up =
                            backlog growth)
      ingest_ship_calls     device_put+insert dispatches in the interval
      ingest_coalesce_mean  staged blocks folded into one dispatch (>=1;
                            1.0 = no coalescing happened = inflow arrived
                            slower than one block per ship)
      ingest_stall_ms       total time producers blocked on backpressure
      ingest_ship_ms        mean dispatch wall time per ship call
      ingest_queue_rows     staged rows not yet shipped (queue depth)
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._t0 = time.monotonic()
        self._rows_in = 0
        self._rows_shipped = 0
        self._blocks_shipped = 0
        self._ship_calls = 0
        self._stall_s = 0.0
        self._ship_s = 0.0

    def record_push(self, rows: int, stall_s: float = 0.0) -> None:
        with self._lock:
            self._rows_in += int(rows)
            self._stall_s += stall_s

    def record_ship(self, rows: int, blocks: int, ship_s: float = 0.0) -> None:
        with self._lock:
            self._rows_shipped += int(rows)
            self._blocks_shipped += int(blocks)
            self._ship_calls += 1
            self._ship_s += ship_s

    def snapshot(self, pending_rows: int = 0, reset: bool = True) -> Dict[str, float]:
        with self._lock:
            dt = max(time.monotonic() - self._t0, 1e-9)
            calls = self._ship_calls
            out = {
                "ingest_rows_per_sec": round(self._rows_shipped / dt, 1),
                "ingest_rows_staged": self._rows_in,
                "ingest_ship_calls": calls,
                "ingest_coalesce_mean": (
                    round(self._blocks_shipped / calls, 3) if calls else 0.0
                ),
                "ingest_stall_ms": round(1000.0 * self._stall_s, 3),
                "ingest_ship_ms": (
                    round(1000.0 * self._ship_s / calls, 3) if calls else 0.0
                ),
                "ingest_queue_rows": int(pending_rows),
            }
            if reset:
                self._t0 = time.monotonic()
                self._rows_in = 0
                self._rows_shipped = 0
                self._blocks_shipped = 0
                self._ship_calls = 0
                self._stall_s = 0.0
                self._ship_s = 0.0
        return out


class ReplayShardStats:
    """Thread-safe counters for the device-replay placement layer
    (replay/device.py; docs/REPLAY_SHARDING.md) — the `replay_*` family
    every train record carries on the device-replay path. Byte counters
    are MEASURED from the device_put result's addressable shards (one
    copy per replica in replicated mode, exactly one owner copy in
    sharded mode), so the bytes-per-row headline is an observation, not
    arithmetic:

      replay_ingest_bytes          h2d bytes landed on devices this
                                   interval (sum over device copies)
      replay_ingest_bytes_per_row  interval mean landed bytes per row —
                                   ~width*4*N replicated, ~width*4
                                   sharded (the 1/N ingest claim;
                                   lower is better)
      replay_shard_count           gauge: storage shards (1 = replicated)
      replay_device_storage_bytes  gauge: storage bytes ONE device holds
                                   (capacity*width*4/N sharded — the N×
                                   aggregate-capacity claim at fixed HBM)
      replay_shard_fill_min/max    gauge: live rows on the emptiest/
                                   fullest shard (strided ownership keeps
                                   them within 1 of each other)
      replay_exchange_ms_p50/p95   interval ship-dispatch tails (the
                                   shard-exchange latency signal)
    """

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self._seed = seed
        self._reset_locked()

    def _reset_locked(self) -> None:
        self._rows = 0
        self._bytes = 0
        self._res = _Reservoir(
            PhaseTimers.RESERVOIR_K,
            (zlib.crc32(b"replay_exchange") ^ self._seed) & 0x7FFFFFFF,
        )

    def record_ship(self, rows: int, nbytes: int, dur_s: float) -> None:
        with self._lock:
            self._rows += int(rows)
            self._bytes += int(nbytes)
            self._res.add(dur_s)

    def snapshot(
        self,
        n_shards: int = 1,
        device_storage_bytes: int = 0,
        fill: int = 0,
        reset: bool = True,
    ) -> Dict[str, float]:
        with self._lock:
            rows = self._rows
            out = {
                "replay_ingest_bytes": self._bytes,
                "replay_ingest_bytes_per_row": (
                    round(self._bytes / rows, 2) if rows else 0.0
                ),
                "replay_shard_count": int(n_shards),
                "replay_device_storage_bytes": int(device_storage_bytes),
                # Shard s owns live logical rows {p < fill : p % N == s}.
                "replay_shard_fill_min": (
                    int(fill) // int(n_shards) if n_shards else 0
                ),
                "replay_shard_fill_max": (
                    -(-int(fill) // int(n_shards)) if n_shards else 0
                ),
                "replay_exchange_ms_p50": round(
                    1000.0 * self._res.percentile(0.50), 3
                ),
                "replay_exchange_ms_p95": round(
                    1000.0 * self._res.percentile(0.95), 3
                ),
            }
            if reset:
                self._reset_locked()
        return out


class MeshStats:
    """Placement facts for the (data, model) mesh (parallel/mesh.py +
    parallel/partition.py; docs/MESH.md) — the `mesh_*` family every
    train/final JSONL record carries on the jax_tpu path. All gauges,
    recomputed at log cadence from leaf SHARDING METADATA only (shapes x
    shard shapes — zero d2h, zero device work):

      mesh_data_axis               the mesh's data-parallel degree
      mesh_model_axis              the mesh's tensor-parallel degree
      mesh_param_bytes_per_device  TrainState bytes (params + targets +
                                   both Adam states) resident on ONE
                                   device — the /model_axis HBM headline
                                   the rule tables buy (docs/MESH.md)
      mesh_param_bytes_total       logical (unsharded) TrainState bytes,
                                   the per-device value's denominator

    No lock: the fields derive from immutable mesh shape + per-leaf
    metadata reads, and only the learner thread snapshots them."""

    def __init__(self, data_axis: int, model_axis: int):
        self._data = int(data_axis)
        self._model = int(model_axis)

    def snapshot(self, state_leaves) -> Dict[str, float]:
        per_device = 0
        total = 0
        for leaf in state_leaves:
            shape = tuple(getattr(leaf, "shape", ()))
            itemsize = int(getattr(getattr(leaf, "dtype", None),
                                   "itemsize", 4))
            n = 1
            for d in shape:
                n *= int(d)
            total += n * itemsize
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None and hasattr(sharding, "shard_shape"):
                m = 1
                for d in sharding.shard_shape(shape):
                    m *= int(d)
                per_device += m * itemsize
            else:
                per_device += n * itemsize
        return {
            "mesh_data_axis": self._data,
            "mesh_model_axis": self._model,
            "mesh_param_bytes_per_device": per_device,
            "mesh_param_bytes_total": total,
        }


def nstep_counters(counts) -> Dict[str, int]:
    """What the actors' n-step accumulators emitted (replay/nstep.py) — the
    `nstep_*` pair on every `train` record of a run with `n_step > 1`, both
    cumulative since the run began:

      nstep_rows        rows emitted, summed over actors
      nstep_short_rows  how many of them carry fewer than n steps: the
                        last n - 1 windows of an episode (flushed at a
                        termination with discount 0, at a truncation
                        with gamma^k, k < n)

    `counts` is a flat [rows, short rows] pair per actor: the pool's
    shared array, which each worker writes at its flushes. No lock: one
    writer a slot, and a torn read is one flush behind."""
    return {
        "nstep_rows": int(sum(counts[0::2])),
        "nstep_short_rows": int(sum(counts[1::2])),
    }


class ForwardMeter:
    """`policy_forward_us` on the `train` records of a run whose host
    workers step a layered policy (config.simba): the mean host time of one
    policy forward, in microseconds, over the forwards all workers made
    since the last record. `counts` is a flat [seconds, forwards] pair per
    worker, the pool's shared array, which each worker adds to after every
    forward (one writer a slot, no lock: a torn read is one forward off).
    An interval without a forward has no key."""

    def __init__(self):
        self._seconds = self._calls = 0.0

    def snapshot(self, counts) -> Dict[str, float]:
        seconds, calls = sum(counts[0::2]), sum(counts[1::2])
        d_seconds, d_calls = seconds - self._seconds, calls - self._calls
        self._seconds, self._calls = seconds, calls
        return {"policy_forward_us": 1e6 * d_seconds / d_calls} if d_calls > 0 else {}


class DevActorStats:
    """Counters for the device-actor subsystem (actors/device_pool.py;
    docs/DEVICE_ACTORS.md) — the `devactor_*` family every train/final
    JSONL record carries when actor_backend='device'. Throughput and the
    per-chunk dispatch tails are interval-scoped (each record describes
    its own window, the IngestStats discipline); restarts and the episode
    counter are cumulative. Single-threaded by construction (only the
    learner thread dispatches rollouts), but locked anyway so a future
    driver thread can't silently race it:

      devactor_rows_per_s   transition rows landed in HBM over the interval
      devactor_chunks       rollout dispatches in the interval
      devactor_chunk_ms     mean wall time per rollout dispatch (enqueue +
                            donated insert — NOT the on-device compute,
                            which overlaps the learner under async dispatch)
      devactor_chunk_p50/p95/max
                            reservoir tails of the same (the per-chunk
                            step-tail signal: a p95 spike means rollout
                            dispatch started synchronizing with the
                            learner stream)
      devactor_env_steps    cumulative env steps produced by this pool
      devactor_episodes     cumulative finished episodes
      devactor_episode_return
                            mean return of episodes finished this interval
      devactor_restarts     cumulative bounded-restart recoveries
    """

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self._seed = seed
        self._t0 = time.monotonic()
        self._rows = 0
        self._chunks = 0
        self._dur_s = 0.0
        self._res = _Reservoir(
            PhaseTimers.RESERVOIR_K,
            (zlib.crc32(b"devactor_chunk") ^ seed) & 0x7FFFFFFF,
        )

    def record_chunk(self, rows: int, dur_s: float) -> None:
        with self._lock:
            self._rows += int(rows)
            self._chunks += 1
            self._dur_s += dur_s
            self._res.add(dur_s)

    def snapshot(self, reset: bool = True) -> Dict[str, float]:
        with self._lock:
            dt = max(time.monotonic() - self._t0, 1e-9)
            n = self._chunks
            out = {
                "devactor_rows_per_s": round(self._rows / dt, 1),
                "devactor_chunks": n,
                "devactor_chunk_ms": (
                    round(1000.0 * self._dur_s / n, 3) if n else 0.0
                ),
                "devactor_chunk_p50": round(
                    1000.0 * self._res.percentile(0.50), 3
                ),
                "devactor_chunk_p95": round(
                    1000.0 * self._res.percentile(0.95), 3
                ),
                "devactor_chunk_max": round(1000.0 * self._res.max, 3),
            }
            if reset:
                self._t0 = time.monotonic()
                self._rows = 0
                self._chunks = 0
                self._dur_s = 0.0
                self._res = _Reservoir(
                    PhaseTimers.RESERVOIR_K,
                    (zlib.crc32(b"devactor_chunk") ^ self._seed) & 0x7FFFFFFF,
                )
        return out


class FusedBeatStats:
    """Counters for the fused training megastep (parallel/megastep.py;
    docs/FUSED_BEAT.md) — the `fused_*` family every train/final JSONL
    record carries when the fused beat is active. All interval-scoped
    (each record describes its own window, the DevActorStats discipline);
    single-threaded by construction (only the learner thread dispatches
    beats), locked anyway like its siblings:

      fused_beats           fused beat dispatches in the interval
      fused_steps_per_s     learner grad steps retired over the interval
      fused_rows_per_s      rollout transition rows landed over the
                            interval (the beat's in-program insert)
      fused_beat_ms         mean wall time per beat dispatch (enqueue +
                            donated-carry sync, one program per beat)
      fused_beat_p50/p95/max
                            reservoir tails of the same (a p95 spike
                            means the single beat program started
                            synchronizing against the host)
      fused_supersteps      superstep DISPATCHES in the interval — equals
                            fused_beats for the plain megastep, and
                            fused_beats / B for a B-beat superstep
                            (parallel/superstep.py): the host overhead
                            a superstep amortizes
      fused_superstep_beats beats per dispatch over the interval (B; 1.0
                            for the plain megastep)
    """

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self._seed = seed
        self._t0 = time.monotonic()
        self._beats = 0
        self._supersteps = 0
        self._steps = 0
        self._rows = 0
        self._dur_s = 0.0
        self._res = _Reservoir(
            PhaseTimers.RESERVOIR_K,
            (zlib.crc32(b"fused_beat") ^ seed) & 0x7FFFFFFF,
        )

    def record_beat(self, learn_steps: int, rows: int, dur_s: float,
                    beats: int = 1) -> None:
        # One call per DISPATCH: a B-beat superstep records its whole
        # loop here (beats=B), so fused_beats keeps counting training
        # beats while the dispatch counter amortizes by B. The duration
        # reservoir keeps whole-dispatch wall times — tails measure what
        # the host actually waits on.
        with self._lock:
            self._beats += int(beats)
            self._supersteps += 1
            self._steps += int(learn_steps)
            self._rows += int(rows)
            self._dur_s += dur_s
            self._res.add(dur_s)

    def snapshot(self, reset: bool = True) -> Dict[str, float]:
        with self._lock:
            dt = max(time.monotonic() - self._t0, 1e-9)
            n = self._beats
            out = {
                "fused_beats": n,
                "fused_steps_per_s": round(self._steps / dt, 1),
                "fused_rows_per_s": round(self._rows / dt, 1),
                "fused_beat_ms": (
                    round(1000.0 * self._dur_s / n, 3) if n else 0.0
                ),
                "fused_beat_p50": round(
                    1000.0 * self._res.percentile(0.50), 3
                ),
                "fused_beat_p95": round(
                    1000.0 * self._res.percentile(0.95), 3
                ),
                "fused_beat_max": round(1000.0 * self._res.max, 3),
                "fused_supersteps": self._supersteps,
                "fused_superstep_beats": (
                    round(n / self._supersteps, 2) if self._supersteps
                    else 0.0
                ),
            }
            if reset:
                self._t0 = time.monotonic()
                self._beats = 0
                self._supersteps = 0
                self._steps = 0
                self._rows = 0
                self._dur_s = 0.0
                self._res = _Reservoir(
                    PhaseTimers.RESERVOIR_K,
                    (zlib.crc32(b"fused_beat") ^ self._seed) & 0x7FFFFFFF,
                )
        return out


class TransferStats:
    """Thread-safe counters for the unified transfer scheduler
    (transfer/scheduler.py; docs/TRANSFER.md) — the scheduler-level
    complement to IngestStats' pipeline view. Per work class (lockstep /
    ingest / prefetch / d2h) it tracks items dispatched, bytes moved, and
    dispatch wall time with a deterministic reservoir for tails; queue
    depths ride in at snapshot time as gauges. snapshot() emits the
    `transfer_*` fields each train record carries and resets the
    interval (restart count and queue depths are cumulative/gauge):

      transfer_dispatches        scheduled items dispatched this interval
      transfer_<cls>_items       per-class dispatches
      transfer_<cls>_bytes       per-class bytes moved
      transfer_<cls>_ms          mean dispatch wall time per item
      transfer_<cls>_p95         reservoir p95 dispatch time (ms)
      transfer_queue_<cls>       current queue depth (gauge)
      transfer_queue_<cls>_max   max depth seen this interval (the
                                 instantaneous gauge is ~0 at the log
                                 cadence — the scheduler drains between
                                 records; the max is the backlog signal)
      transfer_restarts          cumulative scheduler-thread restarts
    """

    # d2h runs inline on the caller thread (scheduler.run_inline) but is
    # accounted identically; it is excluded from transfer_dispatches,
    # which counts the SCHEDULED classes the dispatch thread executed.
    # shard_exchange rides the lockstep deque (one ordered lane) but is
    # accounted as its own class (docs/REPLAY_SHARDING.md).
    SCHEDULED = ("lockstep", "shard_exchange", "ingest", "prefetch", "serve")
    CLASSES = SCHEDULED + ("d2h",)

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self._seed = seed
        self._reset_locked()

    def _reset_locked(self) -> None:
        self._items = {c: 0 for c in self.CLASSES}
        self._bytes = {c: 0 for c in self.CLASSES}
        self._time_s = {c: 0.0 for c in self.CLASSES}
        self._res = {
            c: _Reservoir(64, (zlib.crc32(c.encode()) ^ self._seed) & 0x7FFFFFFF)
            for c in self.CLASSES
        }
        self._depth_max = {c: 0 for c in self.SCHEDULED}

    def record_dispatch(self, cls: str, nbytes: int, dur_s: float) -> None:
        with self._lock:
            if cls not in self._items:
                return
            self._items[cls] += 1
            self._bytes[cls] += int(nbytes)
            self._time_s[cls] += dur_s
            self._res[cls].add(dur_s)

    def record_queue_depth(self, cls: str, depth: int) -> None:
        with self._lock:
            if cls in self._depth_max and depth > self._depth_max[cls]:
                self._depth_max[cls] = depth

    def snapshot(self, queue_depths=None, restarts: int = 0, reset: bool = True) -> Dict[str, float]:
        with self._lock:
            out: Dict[str, float] = {
                "transfer_dispatches": sum(
                    self._items[c] for c in self.SCHEDULED
                ),
                "transfer_restarts": int(restarts),
            }
            for c in self.CLASSES:
                n = self._items[c]
                out[f"transfer_{c}_items"] = n
                out[f"transfer_{c}_bytes"] = self._bytes[c]
                out[f"transfer_{c}_ms"] = (
                    round(1000.0 * self._time_s[c] / n, 3) if n else 0.0
                )
                out[f"transfer_{c}_p95"] = round(
                    1000.0 * self._res[c].percentile(0.95), 3
                )
            for c, d in (queue_depths or {}).items():
                out[f"transfer_queue_{c}"] = int(d)
            for c, d in self._depth_max.items():
                out[f"transfer_queue_{c}_max"] = int(d)
            if reset:
                self._reset_locked()
        return out


class PodStats:
    """Thread-safe pod-resilience counters (parallel/multihost.py;
    docs/RESILIENCE.md pod rows) — the `pod_*` family every train/final
    JSONL record carries on multi-process runs. Counters are CUMULATIVE
    (peer loss and aborts are rare, terminal events; interval-resetting
    them would hide the one record that matters):

      pod_peer_lost               collectives declared lost (deadline
                                  timeout or mid-flight transport error)
      pod_aborts                  coordinated clean aborts taken (the
                                  EXIT_POD_DEGRADED path)
      pod_resume_step_elected     the step the coordinated resume election
                                  agreed on (-1 = no election ran / no
                                  common step)
      pod_beats                   heartbeat-bearing lockstep beats gathered
      pod_collective_near_misses  guarded collectives that consumed > 80%
                                  of their deadline (the tune-the-timeout
                                  signal BEFORE a false PodPeerLost)
      pod_collective_slack_p95_ms deadline headroom at the p95-slowest
                                  collective (deadline - p95 elapsed);
                                  trending toward 0 = deadline too tight

    Elastic-pod events (docs/RESILIENCE.md shrink/grow state machine):

      pod_slices_adopted          replay slice sets adopted at restore
                                  (all-writer checkpoints)
      pod_slice_adopted_step      the step the adopted slice set was
                                  written at (-1 = none; may trail the
                                  elected resume step — replay is allowed
                                  to be a few cadences staler)
      pod_shrinks                 restarts that adopted a slice set from
                                  a LARGER world (training continues at
                                  reduced membership -> degraded)
      pod_grows                   restarts that resharded a smaller
                                  world's slices back up (rejoin ->
                                  healthy)
      pod_state_degraded          1 while the pod trains below the slice
                                  set's writer count, 0 once grown back

    Straggler attribution (obs/aggregate.py; docs/OBSERVABILITY.md §4):

      pod_stragglers              cadences on which the per-host beat-time
                                  detector attributed a straggling host
      pod_straggler_host          the most recently attributed host index
                                  (-1 = never attributed)
    """

    NEAR_MISS_FRAC = 0.8

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self.peer_lost = 0
        self.aborts = 0
        self.resume_step_elected = -1
        self.beats = 0
        self.near_misses = 0
        self.slices_adopted = 0
        self.slice_adopted_step = -1
        self.shrinks = 0
        self.grows = 0
        self.degraded = False
        self.stragglers = 0
        self.straggler_host = -1
        self._deadline_s = 0.0
        self._elapsed = _Reservoir(
            64, (zlib.crc32(b"pod_collective") ^ seed) & 0x7FFFFFFF
        )

    def record_collective(self, elapsed_s: float, deadline_s: float) -> None:
        with self._lock:
            self._deadline_s = deadline_s
            self._elapsed.add(elapsed_s)
            if elapsed_s > self.NEAR_MISS_FRAC * deadline_s:
                self.near_misses += 1

    def record_peer_lost(self) -> None:
        with self._lock:
            self.peer_lost += 1

    def record_abort(self) -> None:
        with self._lock:
            self.aborts += 1

    def record_resume_elected(self, step: int) -> None:
        with self._lock:
            self.resume_step_elected = int(step)

    def record_slice_adopted(self, step: int) -> None:
        with self._lock:
            self.slices_adopted += 1
            self.slice_adopted_step = int(step)

    def record_shrink(self) -> None:
        """Adopted a slice set written by a LARGER world: the pod keeps
        training at reduced membership in a typed degraded state."""
        with self._lock:
            self.shrinks += 1
            self.degraded = True

    def record_grow(self) -> None:
        """Resharded a smaller world's slices back up (rejoin): degraded
        clears — the pod is healthy at its new membership."""
        with self._lock:
            self.grows += 1
            self.degraded = False

    def record_straggler(self, host: int) -> None:
        """One straggler attribution from the pod aggregator's per-host
        beat-time detector (obs/aggregate.py)."""
        with self._lock:
            self.stragglers += 1
            self.straggler_host = int(host)

    def elastic_events(self) -> int:
        """Nonzero when any elastic transition happened — the gate for
        surfacing pod_* fields on runs that shrank to one process
        (train_jax logs pod fields when is_multi OR this)."""
        with self._lock:
            return self.slices_adopted + self.shrinks + self.grows

    def note_beat(self) -> None:
        with self._lock:
            self.beats += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            slack_ms = 0.0
            if self._elapsed.buf and self._deadline_s > 0:
                slack_ms = round(
                    1000.0
                    * (self._deadline_s - self._elapsed.percentile(0.95)),
                    3,
                )
            return {
                "pod_peer_lost": self.peer_lost,
                "pod_aborts": self.aborts,
                "pod_resume_step_elected": self.resume_step_elected,
                "pod_beats": self.beats,
                "pod_collective_near_misses": self.near_misses,
                "pod_collective_slack_p95_ms": slack_ms,
                "pod_slices_adopted": self.slices_adopted,
                "pod_slice_adopted_step": self.slice_adopted_step,
                "pod_shrinks": self.shrinks,
                "pod_grows": self.grows,
                "pod_state_degraded": int(self.degraded),
                "pod_stragglers": self.stragglers,
                "pod_straggler_host": self.straggler_host,
            }


class SupervisorStats:
    """Thread-safe pod-supervisor counters (supervisor/core.py;
    docs/OPERATIONS.md supervisor runbook) — the `supervisor_*` family
    the supervisor's JSONL event stream carries on its final record, so
    a long soak's whole restart history is auditable from one line.
    CUMULATIVE across generations, like PodStats (every event here is a
    rare, decision-bearing transition):

      supervisor_generations      pod generations launched (gen 1 counts)
      supervisor_spawns           child processes spawned, all generations
      supervisor_relaunches       same-membership relaunches (70/75/76 or
                                  untyped crashes)
      supervisor_shrinks          shrink relaunches taken on exit 78
                                  (membership reduced to the survivors)
      supervisor_grows            health-gated grow relaunches (stop-the-
                                  world resize back toward full strength)
      supervisor_backoffs         exponential-backoff waits served
      supervisor_backoff_wait_s   total seconds spent in those waits
      supervisor_breaker_trips    crash-loop circuit-breaker trips (each
                                  one is terminal: the SupervisorGaveUp
                                  report path)
      supervisor_numeric_refusals numeric aborts (77) refused past the
                                  supervisor_max_numeric budget
      supervisor_probe_ready      lost-peer slots that cleared the
                                  K-consecutive-healthy rejoin gate
      supervisor_probe_flaps      healthy->unhealthy probe regressions
                                  (each restarts that slot's gate)
      supervisor_gave_up          1 once the supervisor exited through
                                  the typed give-up path, else 0
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.generations = 0
        self.spawns = 0
        self.relaunches = 0
        self.shrinks = 0
        self.grows = 0
        self.backoffs = 0
        self.backoff_wait_s = 0.0
        self.breaker_trips = 0
        self.numeric_refusals = 0
        self.probe_ready = 0
        self.probe_flaps = 0
        self.gave_up = False

    def record_generation(self, nprocs: int) -> None:
        with self._lock:
            self.generations += 1
            self.spawns += int(nprocs)

    def record_relaunch(self) -> None:
        with self._lock:
            self.relaunches += 1

    def record_shrink(self) -> None:
        with self._lock:
            self.shrinks += 1

    def record_grow(self) -> None:
        with self._lock:
            self.grows += 1

    def record_backoff(self, wait_s: float) -> None:
        with self._lock:
            self.backoffs += 1
            self.backoff_wait_s = round(self.backoff_wait_s + wait_s, 3)

    def record_breaker_trip(self) -> None:
        with self._lock:
            self.breaker_trips += 1
            self.gave_up = True

    def record_numeric_refusal(self) -> None:
        with self._lock:
            self.numeric_refusals += 1
            self.gave_up = True

    def record_probe_ready(self) -> None:
        with self._lock:
            self.probe_ready += 1

    def record_probe_flap(self) -> None:
        with self._lock:
            self.probe_flaps += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {
                "supervisor_generations": self.generations,
                "supervisor_spawns": self.spawns,
                "supervisor_relaunches": self.relaunches,
                "supervisor_shrinks": self.shrinks,
                "supervisor_grows": self.grows,
                "supervisor_backoffs": self.backoffs,
                "supervisor_backoff_wait_s": self.backoff_wait_s,
                "supervisor_breaker_trips": self.breaker_trips,
                "supervisor_numeric_refusals": self.numeric_refusals,
                "supervisor_probe_ready": self.probe_ready,
                "supervisor_probe_flaps": self.probe_flaps,
                "supervisor_gave_up": int(self.gave_up),
            }


class GuardrailStats:
    """Host-side numerical-health counters (guardrails.py;
    docs/RESILIENCE.md 'Numerical health') — the `guardrail_*` family
    every train/final JSONL record carries when guardrails are armed.
    CUMULATIVE like PodStats (divergence events are rare and terminal-ish;
    interval resets would hide the one record that matters):

      guardrail_anomalies          anomalous learner steps (nonfinite +
                                   z-score spikes) — the rollback trigger's
                                   input
      guardrail_nonfinite_steps    steps skipped for a non-finite
                                   TD/grad/param value
      guardrail_loss_spikes        steps skipped by the EWMA z-score
                                   detector (finite but absurd)
      guardrail_skipped_updates    total updates dropped on device
      guardrail_bad_rows           non-finite sampled replay rows seen
      guardrail_rollbacks          checkpoint rollback-repairs taken
      guardrail_last_rollback_step the manifest-valid step the latest
                                   rollback restored (-1 = none)
      guardrail_lr_cooldowns       LR backoff->restore cycles completed
      guardrail_source_quarantines ingest sources quarantined for
                                   repeatedly feeding non-finite rows

    `absorb(health)` mirrors the device probe's cumulative counters and
    returns the DELTA since the previous read — the rolling-window input
    for the rollback trigger (train.py)."""

    def __init__(self):
        self.nonfinite = 0
        self.spikes = 0
        self.skipped = 0
        self.bad_rows = 0
        self.total_steps = 0
        self.rollbacks = 0
        self.last_rollback_step = -1
        self.lr_cooldowns = 0
        self.source_quarantines = 0

    def absorb(self, health: Dict[str, int]) -> Dict[str, int]:
        delta = {
            "nonfinite": int(health.get("nonfinite", 0)) - self.nonfinite,
            "spikes": int(health.get("spikes", 0)) - self.spikes,
            "skipped": int(health.get("skipped", 0)) - self.skipped,
            "bad_rows": int(health.get("bad_rows", 0)) - self.bad_rows,
        }
        self.nonfinite = int(health.get("nonfinite", 0))
        self.spikes = int(health.get("spikes", 0))
        self.skipped = int(health.get("skipped", 0))
        self.bad_rows = int(health.get("bad_rows", 0))
        self.total_steps = int(health.get("total", 0))
        delta["anomalies"] = delta["nonfinite"] + delta["spikes"]
        return delta

    def record_rollback(self, step: int) -> None:
        self.rollbacks += 1
        self.last_rollback_step = int(step)

    def record_lr_cooldown(self) -> None:
        self.lr_cooldowns += 1

    def record_source_quarantine(self) -> None:
        self.source_quarantines += 1

    def snapshot(self) -> Dict[str, int]:
        return {
            "guardrail_anomalies": self.nonfinite + self.spikes,
            "guardrail_nonfinite_steps": self.nonfinite,
            "guardrail_loss_spikes": self.spikes,
            "guardrail_skipped_updates": self.skipped,
            "guardrail_bad_rows": self.bad_rows,
            "guardrail_rollbacks": self.rollbacks,
            "guardrail_last_rollback_step": self.last_rollback_step,
            "guardrail_lr_cooldowns": self.lr_cooldowns,
            "guardrail_source_quarantines": self.source_quarantines,
        }


class ServeStats:
    """Thread-safe counters for the batched policy-inference service
    (serve/; docs/SERVING.md) — the `serve_*` family every train/final
    JSONL record carries when serving is armed, and the digest
    tools.serve_bench emits.

    COUNTERS are cumulative (requests/batches/overloads/errors/refreshes:
    the run's serving history; a nonzero overload anywhere matters even if
    the last interval was quiet). TAILS are interval-scoped: the latency,
    batch-fill, and queue-depth reservoirs reset at snapshot so each
    record's p50/p95 describes its own window — the same PhaseTimers
    reservoir discipline (deterministic seeds) the t_* phases use:

      serve_requests        requests accepted by the batcher (cumulative)
      serve_batches         batches dispatched (cumulative)
      serve_overloads       submissions rejected by the bounded queue —
                            typed ServeOverload backpressure (cumulative)
      serve_errors          batch dispatches that failed; every request in
                            the batch got a typed error (cumulative)
      serve_param_refreshes params reloaded from the broadcast buffer
                            (cumulative)
      serve_fill_mean       rows per dispatched batch / max_batch over the
                            whole run (1.0 = every batch full)
      serve_fill_p50/p95    interval batch-fill fraction tails
      serve_p50_ms/p95_ms/max_ms
                            interval request latency tails, enqueue ->
                            response delivered
      serve_queue_depth     request-queue depth at snapshot (gauge)
      serve_queue_depth_p95 interval p95 of the depth seen at each submit
    """

    def __init__(self, seed: int = 0, max_batch: int = 1):
        self._lock = threading.Lock()
        self._seed = seed
        self.max_batch = max(1, int(max_batch))
        self.requests = 0
        self.batches = 0
        self.batch_rows = 0
        self.overloads = 0
        self.errors = 0
        self.refreshes = 0
        self._reset_reservoirs()

    def _reset_reservoirs(self) -> None:
        def res(name: str) -> _Reservoir:
            return _Reservoir(
                PhaseTimers.RESERVOIR_K,
                (zlib.crc32(name.encode()) ^ self._seed) & 0x7FFFFFFF,
            )

        self._lat = res("serve_latency")
        self._fill = res("serve_fill")
        self._depth = res("serve_depth")

    def record_request(self, queue_depth: int) -> None:
        with self._lock:
            self.requests += 1
            self._depth.add(float(queue_depth))

    def record_batch(self, rows: int, latencies_s) -> None:
        with self._lock:
            self.batches += 1
            self.batch_rows += int(rows)
            self._fill.add(rows / self.max_batch)
            for lat in latencies_s:
                self._lat.add(lat)

    def record_overload(self) -> None:
        with self._lock:
            self.overloads += 1

    def record_error(self) -> None:
        with self._lock:
            self.errors += 1

    def record_refresh(self) -> None:
        with self._lock:
            self.refreshes += 1

    def snapshot(self, queue_depth: int = 0, reset: bool = True) -> Dict[str, float]:
        with self._lock:
            out = {
                "serve_requests": self.requests,
                "serve_batches": self.batches,
                "serve_overloads": self.overloads,
                "serve_errors": self.errors,
                "serve_param_refreshes": self.refreshes,
                "serve_fill_mean": (
                    round(self.batch_rows / (self.batches * self.max_batch), 4)
                    if self.batches
                    else 0.0
                ),
                "serve_fill_p50": round(self._fill.percentile(0.50), 4),
                "serve_fill_p95": round(self._fill.percentile(0.95), 4),
                "serve_p50_ms": round(1000.0 * self._lat.percentile(0.50), 3),
                "serve_p95_ms": round(1000.0 * self._lat.percentile(0.95), 3),
                "serve_max_ms": round(1000.0 * self._lat.max, 3),
                "serve_queue_depth": int(queue_depth),
                "serve_queue_depth_p95": round(self._depth.percentile(0.95), 3),
            }
            if reset:
                self._reset_reservoirs()
        return out


class FrontStats:
    """Thread-safe counters for the network serving front (serve/front/;
    docs/SERVING.md 'Network front') — the `front_*` family every
    train/final JSONL record carries when the front is armed, and the
    digest tools.serve_bench --transport socket emits.

    COUNTERS are cumulative (the run's ingress history — a shed or
    rollback anywhere in the run matters even if the last interval was
    quiet). The wire-latency TAIL is interval-scoped and resets at
    snapshot, the same PhaseTimers reservoir discipline ServeStats uses:

      front_requests        frames accepted over TCP (cumulative)
      front_http_requests   requests accepted over the HTTP adapter
                            (cumulative; NOT a subset of front_requests)
      front_bad_frames      undecodable/oversized frames answered with a
                            typed bad_frame error (cumulative)
      front_sheds           requests rejected by per-tenant QoS before
                            reaching the batcher (cumulative; TenantStats
                            splits this by cause and tenant)
      front_overloads       requests the batcher's bounded queue rejected
                            past QoS admission — typed overload on the
                            wire (cumulative)
      front_timeouts        requests that missed front_timeout_s waiting
                            for their batch — typed timeout (cumulative)
      front_errors          dispatch failures surfaced as typed wire
                            errors (cumulative)
      front_canary_requests requests routed to the candidate version by
                            the deterministic canary split (cumulative)
      front_promotes        candidate versions atomically promoted to
                            stable by the live gate (cumulative)
      front_rollbacks       candidates rolled back by the gate — latency
                            or error-rate regression vs stable
                            (cumulative)
      front_wire_p50_ms/front_wire_p95_ms/front_wire_max_ms
                            interval wire latency tails, frame decoded ->
                            response queued
    """

    def __init__(self, seed: int = 0):
        self._lock = threading.Lock()
        self._seed = seed
        self.requests = 0
        self.http_requests = 0
        self.bad_frames = 0
        self.sheds = 0
        self.overloads = 0
        self.timeouts = 0
        self.errors = 0
        self.canary_requests = 0
        self.promotes = 0
        self.rollbacks = 0
        self._reset_reservoirs()

    def _reset_reservoirs(self) -> None:
        self._wire = _Reservoir(
            PhaseTimers.RESERVOIR_K,
            (zlib.crc32(b"front_wire") ^ self._seed) & 0x7FFFFFFF,
        )

    def record_request(self, http: bool = False) -> None:
        with self._lock:
            if http:
                self.http_requests += 1
            else:
                self.requests += 1

    def record_bad_frame(self) -> None:
        with self._lock:
            self.bad_frames += 1

    def record_shed(self) -> None:
        with self._lock:
            self.sheds += 1

    def record_overload(self) -> None:
        with self._lock:
            self.overloads += 1

    def record_timeout(self) -> None:
        with self._lock:
            self.timeouts += 1

    def record_error(self) -> None:
        with self._lock:
            self.errors += 1

    def record_canary_request(self) -> None:
        with self._lock:
            self.canary_requests += 1

    def record_promote(self) -> None:
        with self._lock:
            self.promotes += 1

    def record_rollback(self) -> None:
        with self._lock:
            self.rollbacks += 1

    def record_wire_latency(self, seconds: float) -> None:
        with self._lock:
            self._wire.add(float(seconds))

    def snapshot(self, reset: bool = True) -> Dict[str, float]:
        with self._lock:
            out = {
                "front_requests": self.requests,
                "front_http_requests": self.http_requests,
                "front_bad_frames": self.bad_frames,
                "front_sheds": self.sheds,
                "front_overloads": self.overloads,
                "front_timeouts": self.timeouts,
                "front_errors": self.errors,
                "front_canary_requests": self.canary_requests,
                "front_promotes": self.promotes,
                "front_rollbacks": self.rollbacks,
                "front_wire_p50_ms": round(
                    1000.0 * self._wire.percentile(0.50), 3
                ),
                "front_wire_p95_ms": round(
                    1000.0 * self._wire.percentile(0.95), 3
                ),
                "front_wire_max_ms": round(1000.0 * self._wire.max, 3),
            }
            if reset:
                self._reset_reservoirs()
        return out


class TenantStats:
    """Thread-safe per-tenant QoS counters (serve/front/qos.py;
    docs/SERVING.md 'Network front') — the `tenant_*` family. All
    cumulative: shed ordering is a run-level contract ("overload sheds
    strictly lowest-priority first"), and the per-tenant split in
    `per_tenant()` is the evidence the shed-ordering test asserts on.

      tenant_count          distinct tenants seen this run
      tenant_served         requests admitted past QoS, all tenants
      tenant_shed_rate      requests shed by a tenant's token bucket
                            (per-tenant rate cap, not overload)
      tenant_shed_priority  requests shed by priority-ordered overload
                            protection (queue depth past the tenant
                            class's threshold)
      tenant_shed_total     tenant_shed_rate + tenant_shed_priority
      tenant_errors         typed errors returned to tenants after
                            admission (dispatch/timeout/overload)
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._tenants: Dict[str, Dict[str, int]] = {}

    def _row(self, tenant: str) -> Dict[str, int]:
        row = self._tenants.get(tenant)
        if row is None:
            row = {"served": 0, "shed_rate": 0, "shed_priority": 0,
                   "errors": 0}
            self._tenants[tenant] = row
        return row

    def record_served(self, tenant: str) -> None:
        with self._lock:
            self._row(tenant)["served"] += 1

    def record_shed(self, tenant: str, cause: str) -> None:
        """cause: 'rate' (token bucket) or 'priority' (overload shed)."""
        with self._lock:
            key = "shed_rate" if cause == "rate" else "shed_priority"
            self._row(tenant)[key] += 1

    def record_error(self, tenant: str) -> None:
        with self._lock:
            self._row(tenant)["errors"] += 1

    def per_tenant(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {t: dict(row) for t, row in self._tenants.items()}

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            rows = list(self._tenants.values())
            shed_rate = sum(r["shed_rate"] for r in rows)
            shed_priority = sum(r["shed_priority"] for r in rows)
            return {
                "tenant_count": len(rows),
                "tenant_served": sum(r["served"] for r in rows),
                "tenant_shed_rate": shed_rate,
                "tenant_shed_priority": shed_priority,
                "tenant_shed_total": shed_rate + shed_priority,
                "tenant_errors": sum(r["errors"] for r in rows),
            }


class Timer:
    """Running steps/sec meter for the actor/learner rate metrics.
    Monotonic clock: a wall-clock jump (NTP step, manual date set) on a
    multi-hour run must not spike or zero the reported rate — the round-5
    Humanoid runs report rates over ~20h windows where this matters."""

    def __init__(self):
        self.reset()

    def reset(self):
        self._t = time.monotonic()
        self._n = 0

    def tick(self, n: int = 1) -> None:
        self._n += n

    def rate(self) -> float:
        dt = time.monotonic() - self._t
        return self._n / dt if dt > 0 else 0.0

    def exclude(self, seconds: float) -> None:
        """Remove `seconds` from the measured window — for off-path work
        (e.g. inline evals) that must not deflate the reported rate."""
        self._t += seconds
