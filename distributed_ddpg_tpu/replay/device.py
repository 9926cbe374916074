"""Device-resident replay: the buffer lives in HBM (SURVEY.md §7 'hard
parts (a)' taken to its conclusion; Podracer-style, PAPERS.md
arXiv 2104.06272).

The host-replay + per-chunk-transfer pipeline pays one h2d transfer per
learner chunk, and transfers that interleave with the execute stream
serialize against it (cost on the chip: not measured). At DDPG scale the
WHOLE buffer fits HBM trivially
(1M transitions x 43 f32 = 172MB on a 16GB v5e), so this module keeps the
packed [capacity, D] ring in device memory:

  - `insert`: one jitted, donated write of a packed [M, D] block at the
    ring's pointer (ring_write: a slice update where the block ends inside
    the ring, a mod-capacity scatter where it wraps); the only steady-state
    h2d traffic is fresh actor data, in bulk, ~1 transfer per thousands of
    env steps.
  - sampling: fused INTO the scanned learner chunk (parallel/learner.py
    sample_chunk path) — jax.random indices + gather per scan step, so a
    K-step chunk needs ZERO transfers in and only td/metrics out.

ptr/size/PRNG key live on device; nothing round-trips. How the ring lies
in HBM follows from its row's width (ring_layout below): narrow rows share
128-lane lines (PackedRing), Humanoid-wide ones are held row-major, and
either way `storage.shape` and `storage[idx]` speak of logical rows.

Ingest pipeline (docs/INGEST.md): pending actor rows stage in a
preallocated host ring (replay/staging.py — one memcpy per push, killing
the seed's O(n^2) np.concatenate), ship as COALESCED super-blocks (up to
max_coalesce staged blocks fold into one device_put + one jitted scatter
per device call, power-of-two group sizes so the compiled-insert cache
stays O(log max_coalesce)), and — single-process, async_ship=True — move
on a background shipper thread so dispatch overlaps learner compute. The
coalesced scatter writes rows at exactly the positions the seed's serial
one-block-at-a-time sequence would have (multi-host groups are transposed
on device to interleave per-process blocks the way serial shipping did),
so storage/ptr/size stay bit-identical — tests/test_ingest_pipeline.py
and the multihost harness assert it.

Multi-host: storage is replicated over the (possibly process-spanning)
mesh, so every process must execute the IDENTICAL insert sequence on the
identical global block — per-process-local inserts would silently fork the
replicas. `add_packed` therefore only buffers host-side when
jax.process_count() > 1, and `sync_ship()` — which all processes must call
at the same point (train_jax: once per learner chunk) — ships
min-over-processes full blocks: each process contributes its local rows
via jax.make_array_from_process_local_data sharded over the mesh's 'data'
axis, and the jitted insert's replicated output sharding makes XLA
all-gather the block (ICI within host, DCN across) into every replica.
Single-process keeps the inline fast path; sync_ship degrades to flush.

Sharded placement (replay_sharding='sharded'; docs/REPLAY_SHARDING.md):
everything above keeps the storage REPLICATED — aggregate replay capacity
equals ONE device's HBM and every ingested row is copied to all N
replicas. Sharded mode partitions the SAME logical ring over the mesh's
'data' axis with strided ownership: logical position p lives on shard
p % N at local slot p // N (NamedSharding P('data', None) over a permuted
physical layout), so per-device storage is capacity/N rows (~N× aggregate
capacity at fixed HBM) and a staged ship device_puts each row ONLY to its
owner shard (~1/N landed ingest bytes — ReplayShardStats measures it from
the addressable shards). The ring SEMANTICS are unchanged: ptr/size, the
insert-position sequence, and every logical row's contents are
bit-identical to replicated mode (the sharded-vs-replicated parity oracle
in tests/test_replay_sharding.py pins it), which is what lets replicated
mode stay the correctness reference the way serial ingest anchored the
coalesced path. Sampling gathers each device's owned rows back into the
global minibatch inside the jitted learner chunk (parallel/learner.py's
masked-gather + psum index exchange). Alignment invariants: capacity and
block_size divide by N, and every insert moves a multiple of N rows, so
ptr % N == 0 always holds and per-shard groups stay exactly even.
Multi-host sharded beats ride the transfer scheduler's shard_exchange
lane (same strict-FIFO ordering + pod deadline as lockstep) and land via
an all-gather + owner-masked local scatter — per-device HBM stays 1/N,
while the DCN wire-byte 1/N (a true all-to-all lowering) is on the
native-TPU verification backlog (ROADMAP).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.metrics import IngestStats, ReplayShardStats
from distributed_ddpg_tpu.replay.staging import HostStagingRing
from distributed_ddpg_tpu.transfer import AdaptiveCoalesce, HostBufferPool
from distributed_ddpg_tpu.types import packed_width


# --- the ring's physical layout in HBM (docs/INGEST.md, "The ring's
# device layout") ---
# The TPU tiles a 2-D f32 array 8 sublanes x 128 lanes over its two minor
# dimensions. For f32[capacity, width] the runtime's own choice puts the
# ROWS minor (XLA `{0,1:T(8,128)}`: feature-major, width padded to 8), which
# wastes nothing but leaves a row as `width` strided words: a gather from it
# is built per element, 55 ns a 43-float row, and at 64 floats a row and more
# XLA will neither gather from nor scatter into it at all: it transposes the
# whole ring to row-major first, once per sampling launch and twice per
# insert, 14 ms each on a 4.35 GB ring (PERF.md PRs 26 and 28). So a row is
# held contiguous, one of two ways, and the width decides which:
#   packed    — at most PACKED_MAX_WIDTH floats: G = 128 // width rows share
#               one 128-lane line of an f32[ceil(capacity / G), 128] array
#               (PackedRing), whose default layout is already row-major.
#   row_major — wider, and the 128-lane padding costs at most
#               ROW_MAJOR_MAX_PAD times the compact row: the [capacity,
#               width] array in the layout `{1,0:T(8,128)}` (ring_format).
#   compact   — everything else, the runtime's own layout: widths in
#               between, and every row-sharded narrow ring.
_SUBLANES, _LANES = 8, 128
ROW_MAJOR_MAX_PAD = 1.25
PACKED_MAX_WIDTH = _LANES // 2


def _ceil_to(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def ring_layout(width: int, sharded: bool = False) -> str:
    """'packed', 'row_major' or 'compact' for a ring of `width` floats a
    row. Packed where two rows or more fit a 128-lane line and the ring is
    not row-sharded (HalfCheetah's 43: two to a line, 256 B a row where
    compact holds 192), on every platform. Row-major, on the TPU alone,
    where the 128-lane padding stays within ROW_MAJOR_MAX_PAD of the
    compact row (Humanoid's 772 -> 896 is in; Ant's 65 -> 128 is out)."""
    if width <= PACKED_MAX_WIDTH and not sharded:
        return "packed"
    if _ceil_to(width, _LANES) <= ROW_MAJOR_MAX_PAD * _ceil_to(width, _SUBLANES):
        return "row_major"
    return "compact"


def _packing(width: int):
    """(G, stride) of a packed ring: rows to a 128-lane line, and the lanes
    from one row's start to the next."""
    g = _LANES // width
    return g, _LANES // g


def ring_row_bytes(width: int, layout: str) -> int:
    """Bytes one ring row holds in HBM under `layout`, padding included
    (packed: a line's 512 over its rows, rounded down)."""
    if layout == "packed":
        return 4 * _LANES // _packing(width)[0]
    return 4 * _ceil_to(width, _LANES if layout == "row_major" else _SUBLANES)


@jax.tree_util.register_pytree_node_class
class PackedRing:
    """A ring of `capacity` logical rows of `width` <= 64 floats, held as
    f32[ceil(capacity / G), 128] lines with G = 128 // width rows to a line:
    row r lies at lanes (r % G) * stride .. + width of line r // G, stride =
    128 // G. One leaf (the lines) and static (width, capacity), so it
    passes through jit, donation, shard_map and device_put as the array it
    stands for, answers `.shape` and `ring[idx]` with logical rows, and a
    program that says `storage[idx]` takes it or a plain [capacity, width]
    array alike. `np.asarray(ring)` is the logical rows."""

    def __init__(self, lines, width: int, capacity: int):
        self.lines, self.width, self.capacity = lines, int(width), int(capacity)

    def tree_flatten(self):
        return (self.lines,), (self.width, self.capacity)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], *aux)

    @staticmethod
    def n_lines(width: int, capacity: int) -> int:
        return -(-capacity // _packing(width)[0])

    @classmethod
    def from_rows(cls, rows: np.ndarray) -> "PackedRing":
        """Host rows [capacity, width] as host lines."""
        capacity, width = rows.shape
        (g, stride), n = _packing(width), cls.n_lines(width, capacity)
        slots = np.zeros((n * g, stride), np.float32)
        slots[:capacity, :width] = rows
        lines = np.zeros((n, _LANES), np.float32)
        lines[:, : g * stride] = slots.reshape(n, g * stride)
        return cls(lines, width, capacity)

    def __array__(self, dtype=None, copy=None):
        g, stride = _packing(self.width)
        slots = np.asarray(self.lines)[:, : g * stride].reshape(-1, stride)
        return np.ascontiguousarray(
            slots[: self.capacity, : self.width], dtype=dtype
        )

    @property
    def shape(self):
        return self.capacity, self.width

    @property
    def sharding(self):
        return self.lines.sharding

    def devices(self):
        return self.lines.devices()

    def __getitem__(self, key):
        """Logical rows `key` (an integer array of any shape, an int or a
        slice), and optionally a slice of their columns: one gather of
        whole lines and a select on the slot. jnp.where, not
        take_along_axis, which lowers to a second gather."""
        cols = slice(None)
        if isinstance(key, tuple):
            key, cols = key
        if isinstance(key, slice):
            key = np.arange(*key.indices(self.capacity), dtype=np.int32)
        idx = jnp.asarray(key)
        g, stride = _packing(self.width)
        per_line = jnp.asarray(g, idx.dtype)
        lines = self.lines[jax.lax.div(idx, per_line)]
        slot = jax.lax.rem(idx, per_line)[..., None]
        rows = lines[..., : self.width]
        for k in range(1, g):
            at = k * stride
            rows = jnp.where(slot == k, lines[..., at : at + self.width], rows)
        return rows[..., cols]

    def write(self, block, ptr, offset=None) -> "PackedRing":
        """The ring with block row j at logical row (ptr + offset[j]) %
        capacity, `offset` a permutation of arange(m) (default: itself) and
        m <= capacity. Whole lines: the at most m // G + 3 lines the run
        touches (an unaligned start, and the ring's last line where G does
        not divide the capacity) are read, overlaid and written back, one
        gather and one scatter, so `ptr` needs no alignment. A `[1, width]`
        window scattered at (line, lane) would be plainer and compiles, on
        the TPU, to a loop of m dynamic-update-slices."""
        m = block.shape[0]
        (g, stride), cap = _packing(self.width), self.capacity
        n = self.lines.shape[0]
        touched = min(n, m // g + 3)
        line = (ptr // g + jnp.arange(touched, dtype=jnp.int32)) % n
        src = (
            None if offset is None
            else jnp.zeros(m, jnp.int32).at[offset].set(jnp.arange(m, dtype=jnp.int32))
        )
        lane = jnp.arange(stride, dtype=jnp.int32)[None, :] < self.width
        new, fresh = [], []
        for k in range(g):
            row = line * g + k
            j = (row - ptr) % cap
            fresh.append(((row < cap) & (j < m))[:, None] & lane)
            j = jnp.minimum(j, m - 1)
            rows = block[j if src is None else src[j]]
            new.append(jnp.pad(rows, ((0, 0), (0, stride - self.width))))
        pad = ((0, 0), (0, _LANES - g * stride))
        overlaid = jnp.where(
            jnp.pad(jnp.concatenate(fresh, axis=1), pad),
            jnp.pad(jnp.concatenate(new, axis=1), pad),
            self.lines[line],
        )
        return PackedRing(self.lines.at[line].set(overlaid), self.width, cap)


def run_fits(ptr, m: int, capacity: int, in_order: bool = True):
    """ring_write's rule for a plain ring, in the one place both its program
    and the host's count of what it did (DeviceReplay._note_shipped) read
    it: False where an m-row block is no run of consecutive ring rows (its
    rows permuted, or more of them than the ring holds), else whether the
    run that starts at `ptr` ends at or before the ring's last row: a traced
    bool of a traced pointer, a Python bool of the host's mirror."""
    if not in_order or m > capacity:
        return False
    return ptr + m <= capacity


def ring_write(storage, block, ptr, offset=None):
    """The one ring insert: `storage` with block row j at logical row (ptr +
    offset[j]) % capacity (offset: a permutation of arange(m), default
    itself), for a PackedRing and for a plain [capacity, width] array.

    A plain ring takes a block in ring order (no `offset`, m <= capacity)
    that ends at or before the ring's last row as what it is, one run of
    consecutive rows: a dynamic-update-slice at `ptr`, the speed of a copy,
    where m computed indices make a row scatter (12 GB/s for 1 KB rows on a
    v5e; PERF.md PR 41). Only a block that passes the ring's end is
    scattered, under the other branch of a `cond` on `ptr`; XLA updates the
    donated ring in place under either branch."""
    if isinstance(storage, PackedRing):
        return storage.write(block, ptr, offset)
    m, capacity = block.shape[0], storage.shape[0]
    in_order = offset is None
    if in_order:
        offset = jnp.arange(m, dtype=jnp.int32)
    fits = run_fits(ptr, m, capacity, in_order)

    def scatter(storage):
        return storage.at[(ptr + offset) % capacity].set(block)

    if fits is False:
        return scatter(storage)
    return jax.lax.cond(
        fits,
        lambda storage: jax.lax.dynamic_update_slice(storage, block, (ptr, 0)),
        scatter,
        storage,
    )


def ring_format(sharding, width: int):
    """What every program that creates, restores or returns the ring names
    for it: `sharding` plus the row-major device layout where ring_layout
    picks it, and the plain `sharding` otherwise — packed lines (whose
    default layout is the row-major one), compact rows, no mesh, or devices
    that are not TPUs. Programs that only READ the ring name no layout: jit
    adopts a committed argument's."""
    if (
        sharding is None
        or next(iter(sharding.device_set)).platform != "tpu"
        or ring_layout(width) != "row_major"
    ):
        return sharding
    return Format(
        Layout(major_to_minor=(0, 1), tiling=((_SUBLANES, _LANES),)), sharding
    )


_UNCACHED_COMPILE = threading.Lock()


class _RingProgram:
    """A jitted program that RETURNS the row-major ring, compiled once per
    argument shapes with the persistent compile cache out of the way and
    dispatched through that executable. Such a program must never be loaded
    from the cache: this runtime (jax 0.9.0, libtpu 0.0.34) labels every
    output of a deserialized executable with the DEFAULT layout, whatever
    it was compiled for. The buffer is row-major, `.format` says
    feature-major, and the next program either refuses the ring ("Layout
    passed to jit does not match") or is compiled for a layout the buffer
    does not have (my chip runs, PR 26; PERF.md §6). The switch is
    process-wide, hence the lock; a compile on another thread meanwhile
    misses the cache once and nothing else. `lower` is the jit's own."""

    def __init__(self, jitted):
        self._jitted = jitted
        self._compiled = {}
        self.lower = jitted.lower

    def __call__(self, *args):
        return self.compiled(*args)(*args)

    def compiled(self, *args):
        """The executable for `args` (arrays or ShapeDtypeStructs), built
        on first sight of their shapes."""
        shapes = tuple(np.shape(a) for a in jax.tree.leaves(args))
        if shapes not in self._compiled:
            self._compiled[shapes] = self._compile(self._jitted.lower(*args))
        return self._compiled[shapes]

    @staticmethod
    def _compile(lowered):
        from jax.experimental.compilation_cache import compilation_cache

        with _UNCACHED_COMPILE:
            was = jax.config.jax_enable_compilation_cache
            jax.config.update("jax_enable_compilation_cache", False)
            compilation_cache.reset_cache()  # the decision is latched
            try:
                return lowered.compile()
            finally:
                jax.config.update("jax_enable_compilation_cache", was)
                compilation_cache.reset_cache()


class IngestError(RuntimeError):
    """The background ingest shipper thread died; the original exception
    rides along as __cause__ (mirrors ChunkPrefetcher's 'prefetch thread
    died' surfacing discipline)."""


class ReplayUsageError(RuntimeError):
    """The caller used a device-replay entry point outside its supported
    mode (per-process drain in a pod, single-writer checkpoint of a
    sharded buffer, ...). Distinct from IngestError — nothing died; the
    call itself is wrong, and recovery is a config/callsite change, never
    a restart."""


class _IngestShipper:
    """Single-process background shipper: moves staged full blocks to HBM
    off the producer's critical path, mirroring ChunkPrefetcher's
    daemon-thread discipline. The bounded double buffer is the staging
    ring itself: a full ring blocks producers inside add_packed (stall
    time is counted in IngestStats), which is the backpressure that keeps
    host memory bounded while dispatch overlaps learner compute."""

    def __init__(self, replay: "DeviceReplay"):
        self._replay = replay
        self._stop = threading.Event()
        self.exc: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="ingest-ship"
        )

    def start(self) -> "_IngestShipper":
        self._thread.start()
        return self

    def _run(self) -> None:
        r = self._replay
        try:
            while not self._stop.is_set():
                with r._staging:
                    while (
                        len(r._ring) < r.block_size
                        and not self._stop.is_set()
                    ):
                        r._staging.wait(0.1)
                if self._stop.is_set():
                    return
                r._drain_ring()
        except BaseException as e:  # surface in the producer's next call
            self.exc = e
            with r._staging:
                r._staging.notify_all()  # unblock backpressure waiters

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        with self._replay._staging:
            self._replay._staging.notify_all()
        self._thread.join(timeout=timeout)


class DeviceReplay:
    def __init__(
        self,
        capacity: int,
        obs_dim: int,
        act_dim: int,
        mesh: Optional[Mesh] = None,
        block_size: int = 4096,
        seed: int = 0,
        async_ship: bool = False,
        max_coalesce: int = 8,
        staging_blocks: int = 16,
        fault=None,
        scheduler=None,
        adaptive_coalesce: bool = False,
        host_pool: bool = False,
        background_sync: bool = False,
        pod_fault=None,
        track_sources: bool = False,
        replay_sharding: str = "replicated",
    ):
        self.capacity = int(capacity)
        self.obs_dim = obs_dim
        self.act_dim = act_dim
        self.block_size = int(block_size)
        self.width = packed_width(obs_dim, act_dim)
        self._mesh = mesh
        if replay_sharding not in ("replicated", "sharded"):
            raise ValueError(
                f"replay_sharding must be 'replicated' or 'sharded', got "
                f"{replay_sharding!r}"
            )
        self.sharded = replay_sharding == "sharded"
        if self.sharded:
            # Strided ownership (module docstring): logical position p is
            # owned by shard p % N at local slot p // N. The alignment
            # invariants below keep ptr % N == 0 through every insert and
            # wrap, so per-shard ship groups are always exactly even.
            if mesh is None:
                raise ValueError(
                    "replay_sharding='sharded' partitions storage over a "
                    "mesh; construct the replay with one"
                )
            # 2D composition (docs/MESH.md): the ring partitions over the
            # 'data' axis only — under model_axis > 1 every storage spec
            # below names just 'data', so each shard's rows replicate
            # across the 'model' axis (per-device HBM is capacity /
            # data_axis) and the shard_map insert/gather bodies run
            # identically on every model replica.
            self._n_shards = int(mesh.shape["data"])
            if self.capacity % self._n_shards:
                raise ValueError(
                    f"replay_capacity {self.capacity} must divide evenly "
                    f"over {self._n_shards} shards (mod-capacity wraparound "
                    "must preserve the position's owner residue)"
                )
            if self.block_size % self._n_shards:
                raise ValueError(
                    f"block_size {self.block_size} must divide evenly over "
                    f"{self._n_shards} shards (each ship lands rows on "
                    "every owner in exactly even groups)"
                )
            self._shard_cap = self.capacity // self._n_shards
        else:
            self._n_shards = 1
            self._shard_cap = self.capacity
        sharding = (
            NamedSharding(mesh, P("data", None) if self.sharded else P(None, None))
            if mesh is not None
            else None
        )
        scalar_sharding = NamedSharding(mesh, P()) if mesh is not None else None
        # The ring's Format (ring_format): the plain sharding (None without
        # a mesh), or sharding plus the row-major layout. Every program that
        # returns the ring names it, here and in the fused beat and the
        # superstep, or a donated insert would hand the ring back in the
        # default layout and silently undo it.
        self.storage_format = ring_format(sharding, self.width)
        # What says it engaged (ingest_snapshot's replay_ring_layout /
        # replay_row_bytes_device): the layout the ring is held in and the
        # bytes a row takes there — a packed line's share everywhere, tiled
        # and padded on the TPU, the bare row elsewhere.
        layout = ring_layout(self.width, self.sharded)
        self.ring_layout = (
            layout
            if layout == "packed" or isinstance(self.storage_format, Format)
            else "compact"
        )
        self.storage = self._place_storage(None)
        self.row_bytes_device = (
            ring_row_bytes(self.width, self.ring_layout)
            if self.ring_layout == "packed"
            or next(iter(self.storage.devices())).platform == "tpu"
            else 4 * self.width
        )
        self.ptr = jnp.zeros((), jnp.int32)
        self.size = jnp.zeros((), jnp.int32)
        if sharding is not None:
            self.ptr = jax.device_put(self.ptr, scalar_sharding)
            self.size = jax.device_put(self.size, scalar_sharding)
        # Placement-layer observability (metrics.ReplayShardStats): landed
        # h2d bytes are MEASURED from each ship's addressable shards, so
        # the bytes-per-row A/B headline (docs/REPLAY_SHARDING.md) is an
        # observation of what this process actually moved.
        self._shard_stats = ReplayShardStats(seed=seed)

        # --- ingest pipeline state (docs/INGEST.md) ---
        # Staging ring + condition: producers push under it, the shipper /
        # sync paths pop under it, and backpressure waits on it. The
        # dispatch lock serializes every device-op sequence that reads or
        # swaps storage/ptr/size (ship calls here, chunk dispatch in
        # parallel/learner.py) so a donated-away storage buffer is never
        # observable mid-swap from another thread.
        self._max_coalesce = max(1, int(max_coalesce))
        self._ring = HostStagingRing(
            self.width, max(1, int(staging_blocks)) * self.block_size
        )
        self._staging = threading.Condition()
        self.dispatch_lock = threading.RLock()
        self._stats = IngestStats()
        # Chaos harness (faults.py): an optional FaultSite ticked once per
        # ship dispatch — shipper:ship:slow@k sleeps, shipper:ship:crash@k
        # raises (killing the shipper thread, which _check_shipper then
        # restarts — the supervised-recovery path under test).
        self._fault = fault
        # Pod chaos site (faults.py pod:<proc>:kill|hang@beat): ticked once
        # per lockstep sync_ship beat, so the beat ordinal is the trigger —
        # identical on every process, which is what lets a scripted
        # single-process death land at a deterministic pod-wide point.
        # train_jax arms it via arm_pod_fault at the first POST-WARMUP
        # beat: warmup's beat count is wall-clock-dependent (actor startup
        # pacing), steady-state beats advance one per lockstep chunk.
        self._pod_fault = pod_fault
        self._shipper_restarts = 0
        self._max_shipper_restarts = 3

        donate = partial(
            jax.jit,
            donate_argnums=(0,),
            **(
                dict(
                    in_shardings=(
                        self.storage_format, sharding, scalar_sharding,
                        scalar_sharding,
                    ),
                    out_shardings=(
                        self.storage_format, scalar_sharding, scalar_sharding
                    ),
                )
                if sharding is not None
                else {}
            ),
        )

        # Function names below are the programs' names in a device trace
        # (`jit_ring_insert...` on the XLA Modules line): every insert
        # program shares the prefix, and the benchmark's ingest readers
        # find them by it.
        def ring_insert(storage, block, ptr, size):
            m = block.shape[0]
            storage = ring_write(storage, block, ptr)
            new_ptr = (ptr + m) % self.capacity
            new_size = jnp.minimum(size + m, self.capacity)
            return storage, new_ptr, new_size

        # Pure insert body, kept for composition inside LARGER jitted
        # programs (the fused megastep, parallel/megastep.py) — the jitted
        # wrappers below own donation/shardings for standalone dispatch.
        self._insert_pure = ring_insert

        # One jitted program per super-block shape; shapes are restricted
        # to power-of-two multiples of block_size (_coalesce_k), so the
        # jit cache holds at most log2(max_coalesce)+1 entries. In sharded
        # mode the replicated-storage program is never built — the
        # per-shard scatter caches below replace it (same bounded set of
        # shapes, one program per m).
        self._insert = (
            None if self.sharded else self.ring_program(donate(ring_insert))
        )
        if isinstance(self._insert, _RingProgram):  # row-major, not sharded
            # No cache to load them from in a moment (_RingProgram), so the
            # super-block shapes compile here, in set-up, and not under the
            # dispatch lock at each one's first ship.
            k = 1
            while k <= self._max_coalesce:
                self._insert.compiled(
                    self.storage,
                    jax.ShapeDtypeStruct(
                        (k * self.block_size, self.width), jnp.float32,
                        sharding=sharding,
                    ),
                    self.ptr, self.size,
                )
                k *= 2
        if self.sharded:
            self._block_sharding_sharded = NamedSharding(mesh, P("data", None))
            self._scalar_sharding = scalar_sharding
            self._insert_grouped_cache = {}
            self._insert_replrows_cache = {}
            # Restore-time reshard programs (elastic pod): land a full
            # replicated LOGICAL state onto this mesh's owners, whatever
            # process count wrote it (_get_reshard; docs/REPLAY_SHARDING.md
            # all-writer checkpoints).
            self._reshard_cache = {}

        # Multi-host ingest (see module docstring): a second compiled insert
        # whose block input is SHARDED over the data axis — each process
        # feeds its local rows, XLA all-gathers into the replicated storage.
        self._procs = jax.process_count() if mesh is not None else 1
        if self._procs > 1:
            global_rows = self._procs * self.block_size
            if global_rows % mesh.shape["data"]:
                raise ValueError(
                    f"block_size {self.block_size} x {self._procs} processes "
                    f"must divide evenly over data axis {mesh.shape['data']}"
                )
            self._block_sharding = NamedSharding(mesh, P("data", None))
            self._global_in_shardings = (
                self.storage_format, self._block_sharding, scalar_sharding,
                scalar_sharding,
            )
            self._global_out_shardings = (
                self.storage_format, scalar_sharding, scalar_sharding
            )
            self._insert_global_cache = {}
            self._insert_global_sharded_cache = {}

        # --- unified transfer scheduler integration (docs/TRANSFER.md) ---
        # When a TransferScheduler is attached, single-process async
        # shipping submits ingest work items to it instead of running the
        # private _IngestShipper thread, the coalesce cap can adapt, a
        # host-buffer pool recycles the super-block staging copies, and
        # multi-host sync_ship beats can run on the scheduler's lockstep
        # lane in the background.
        self._sched = scheduler
        self._adaptive = (
            AdaptiveCoalesce(hi=self._max_coalesce, block_size=self.block_size)
            if adaptive_coalesce and self._max_coalesce > 1
            else None
        )
        self._pool = HostBufferPool(self.width) if host_pool else None
        self._ingest_inflight = False
        self._ingest_ticket = None
        self._ingest_exc: Optional[BaseException] = None
        self._bg_sync = (
            bool(background_sync) and scheduler is not None and self._procs > 1
        )
        self._beat = 0

        # --- ingest-source attribution (guardrails.py bad-row quarantine) ---
        # A host-side mirror of "which actor slot produced the row at each
        # storage position": add_packed tags staged rows with a source id,
        # a FIFO of (source, count) runs parallel to the staging ring, and
        # every successful ship stamps the landed positions using a host
        # mirror of the device insert pointer (advanced only on success,
        # exactly like the device ptr). Multi-host stamps only THIS
        # process's interleave slots (each process drains — and can
        # quarantine — only its own workers). Off (default): zero
        # bookkeeping, sources_of reports -1 (untracked).
        self._track_sources = bool(track_sources)
        self._source_map = (
            np.full(self.capacity, -1, np.int32)
            if self._track_sources else None
        )
        self._src_fifo: deque = deque()  # mutable [source, rows] run-lengths
        self._host_ptr = 0
        # Rows landed since this ring was built or restored, counted on the
        # host at each successful ship, and the write pointer it began at:
        # `ring_wraps` (ingest_snapshot) reads them, with no d2h. From the
        # same two, at each landed insert (_note_shipped): how many passed
        # the ring's end (`replay_insert_wrapped`, any layout) and how many
        # ring_write wrote as one run (`replay_insert_runs`, by its own
        # rule, run_fits, where the insert programs go through its plain
        # branch: the sharded ones do not, and a packed ring has its own).
        self._rows_landed = 0
        self._ptr_start = 0
        self._insert_runs = 0
        self._inserts_wrapped = 0
        self._plain_ring_write = not (
            self.sharded or isinstance(self.storage, PackedRing)
        )
        self._proc_idx = jax.process_index() if self._procs > 1 else 0

        # Background shipper (single-process only: multi-host rows may
        # leave the host ONLY via the lockstep sync_ship collective).
        self._async = bool(async_ship) and self._procs == 1
        self._sched_ingest = self._async and self._sched is not None
        self._shipper = (
            _IngestShipper(self).start()
            if self._async and not self._sched_ingest
            else None
        )

    def __len__(self) -> int:
        return int(jax.device_get(self.size))

    def _place_storage(self, rows: Optional[np.ndarray]):
        """The ring on the device in its Format (ring_format): zeros when
        `rows` is None, else the restored PHYSICAL rows. Row-major zeros
        are made in place by a program whose output names the Format —
        allocating in the default layout and converting would hold two
        rings in HBM. (A restore does hold two while its rows are relaid:
        they land in the default layout first.)"""
        fmt = self.storage_format
        if self.ring_layout == "packed":
            storage = (
                PackedRing(
                    jnp.zeros(
                        (PackedRing.n_lines(self.width, self.capacity), _LANES),
                        jnp.float32,
                    ),
                    self.width, self.capacity,
                )
                if rows is None
                else PackedRing.from_rows(rows)
            )
            return jax.device_put(storage, fmt)
        if isinstance(fmt, Format):
            if rows is not None:
                relay = self.ring_program(jax.jit(lambda x: x, out_shardings=fmt))
                return relay(rows)
            zeros = self.ring_program(
                jax.jit(
                    partial(jnp.zeros, (self.capacity, self.width), jnp.float32),
                    out_shardings=fmt,
                )
            )
            return zeros()
        storage = (
            jnp.zeros((self.capacity, self.width), jnp.float32)
            if rows is None
            else jnp.asarray(rows)
        )
        return storage if fmt is None else jax.device_put(storage, fmt)

    def reward_sample(self, max_n: int = 100_000):
        """(reward, discount) columns, up to max_n rows, pulled to host —
        feeds the C51 auto-support sizing (ops/support_auto.initial_bounds;
        discount==0 marks terminal transitions, whose one-off rewards must
        not enter the persistent-reward bound).
        One bounded d2h outside the hot loop. Multi-process: REPLICATED
        storage only — the staging ring holds process-LOCAL un-shipped
        rows, and per-process bounds derived from them would compile
        different Bellman targets per replica (the replica fork this
        module's insert discipline exists to prevent). Single-process
        includes staged rows so a just-warmed buffer is fully
        represented."""
        col = self.obs_dim + self.act_dim
        # dispatch_lock: the async shipper's insert DONATES storage, so an
        # unlocked read here could dispatch against a deleted buffer.
        with self.dispatch_lock:
            size = len(self)
            n = min(size, max_n)
            if self.sharded:
                # Logical rows live strided across shards: map the sample
                # (full fill, or the same deterministic stride as the
                # replicated branch) through the placement and gather.
                # Same logical rows as replicated mode -> identical
                # support-sizing decisions (the replica-fork rule below).
                idx = (
                    np.arange(size, dtype=np.int64)
                    if n == size
                    else np.linspace(0, size - 1, n).astype(np.int64)
                )
                cols = np.asarray(
                    jax.device_get(
                        jnp.take(
                            self.storage[:, col : col + 2],
                            jnp.asarray(self._phys_of_logical(idx)),
                            axis=0,
                        )
                    )
                )
            else:
                # All of a young ring; of a larger one, rows evenly strided
                # over the live region, not the [:n] prefix — a 1M-ring
                # prefix can be ~900k insertions stale, and the round-5
                # corroboration gate would refuse legitimate expansions
                # against long-gone rewards. Deterministic stride: replicas
                # and strict_sync replays see identical samples.
                idx = np.linspace(0, size - 1, n).astype(np.int32)
                cols = np.asarray(
                    jax.device_get(self.storage[idx, col : col + 2])
                )
        if self._procs == 1:
            with self._staging:
                pend = self._ring.peek_cols(col, 2, max_n)
            if len(pend):
                cols = np.concatenate([cols, pend])
        return cols[:, 0], cols[:, 1]

    @property
    def pending_rows(self) -> int:
        """Host-side rows staged but not yet shipped (multi-host: waiting
        for the lockstep sync_ship; callers use this for backpressure)."""
        with self._staging:
            return len(self._ring)

    def ingest_snapshot(self) -> dict:
        """Interval ingest observability fields (metrics.IngestStats):
        rows/sec shipped, ship calls, coalesce factor, producer stall
        time, queue depth — emitted into train records. The shipper
        restart count (cumulative, recovery path) rides along."""
        out = self._stats.snapshot(pending_rows=self.pending_rows)
        out["ingest_shipper_restarts"] = self._shipper_restarts
        # Placement-layer fields (replay_* family, docs/REPLAY_SHARDING.md):
        # measured landed bytes/row, per-device storage bytes (rows as the
        # device pads them), per-shard fill, exchange-dispatch tails.
        out.update(
            self._shard_stats.snapshot(
                n_shards=self._n_shards,
                device_storage_bytes=(
                    4 * _LANES * PackedRing.n_lines(self.width, self.capacity)
                    if self.ring_layout == "packed"
                    else self.capacity * self.row_bytes_device // self._n_shards
                ),
                fill=len(self),
            )
        )
        # Times the write pointer has passed the ring's end in this run: 0
        # while the ring fills, 1 from the moment a draw ranges over all of it.
        out["ring_wraps"] = (
            (self._ptr_start + self._rows_landed) // self.capacity
        )
        out["replay_insert_runs"] = self._insert_runs
        out["replay_insert_wrapped"] = self._inserts_wrapped
        out["replay_ring_layout"] = self.ring_layout
        out["replay_row_bytes_device"] = self.row_bytes_device
        return out

    def transfer_snapshot(self) -> dict:
        """Replay-owned transfer_* fields: the adaptive-coalesce
        trajectory and host-pool gauges (the scheduler's own counters ride
        TransferScheduler.snapshot; train.py merges both)."""
        out = {}
        if self._adaptive is not None:
            out.update(self._adaptive.snapshot())
        if self._pool is not None:
            out.update(self._pool.snapshot())
        return out

    def arm_pod_fault(self, site) -> None:
        """Attach the pod chaos site (see __init__). Armed late so the
        trigger ordinal counts beats from a deterministic point (the
        warmup/steady boundary is lockstep on every process)."""
        self._pod_fault = site

    def close(self) -> None:
        """Stop the background shipper (if any) and detach from the
        transfer scheduler; subsequent add_packed calls fall back to
        inline shipping, so teardown stragglers still land."""
        if self._shipper is not None:
            self._shipper.stop()
            self._shipper = None
            self._async = False
        if self._sched_ingest:
            self._sched_ingest = False
            self._async = False

    # --- host -> HBM ingestion ---

    def _check_shipper(self) -> None:
        """Surface — or recover from — a dead shipper thread. The shipper
        is stateless between ships (staged rows stay in the ring until a
        pop commits to a dispatch... except the in-flight super-block a
        crash mid-ship loses, bounded by max_coalesce * block_size rows),
        so a bounded number of restarts is safe; past the cap the failure
        is structural and must surface."""
        s = self._shipper
        if s is not None and s.exc is not None:
            if self._shipper_restarts < self._max_shipper_restarts:
                self._shipper_restarts += 1
                exc, s.exc = s.exc, None
                trace.instant("shipper_restart", n=self._shipper_restarts)
                import sys

                print(
                    f"[ingest] shipper thread died ({exc!r}); restarting "
                    f"({self._shipper_restarts}/"
                    f"{self._max_shipper_restarts})",
                    file=sys.stderr, flush=True,
                )
                self._shipper = _IngestShipper(self).start()
                return
            raise IngestError("ingest shipper thread died") from s.exc
        # Scheduler-path equivalent: a failed ingest work item (its own
        # exception, or a scheduler-thread death that failed the ticket
        # before the item ran) recovers through the same bounded-restart
        # budget — resubmit up to the cap, then IngestError.
        t = self._ingest_ticket
        if t is not None and t.done() and t.exception is not None:
            with self._staging:
                self._ingest_inflight = False
            self._ingest_exc = self._ingest_exc or t.exception
            self._ingest_ticket = None
        exc = self._ingest_exc
        if exc is not None:
            self._ingest_exc = None
            if self._shipper_restarts < self._max_shipper_restarts:
                self._shipper_restarts += 1
                trace.instant("shipper_restart", n=self._shipper_restarts)
                import sys

                print(
                    f"[ingest] transfer ingest work died ({exc!r}); "
                    f"resubmitting ({self._shipper_restarts}/"
                    f"{self._max_shipper_restarts})",
                    file=sys.stderr, flush=True,
                )
                if self._sched_ingest:
                    with self._staging:
                        self._submit_ingest_locked()
                return
            raise IngestError("ingest shipper thread died") from exc

    # --- ingest-source attribution helpers (see __init__) ---

    def _pop_sources_locked(self, n: int) -> Optional[np.ndarray]:
        """Consume n rows' worth of source tags from the FIFO (caller holds
        _staging, at the same moment it pops the ring so the two stay in
        lockstep). Padding/short entries report -1."""
        if not self._track_sources:
            return None
        out = np.full(n, -1, np.int32)
        i = 0
        while i < n and self._src_fifo:
            entry = self._src_fifo[0]
            take = min(entry[1], n - i)
            out[i : i + take] = entry[0]
            entry[1] -= take
            if entry[1] == 0:
                self._src_fifo.popleft()
            i += take
        return out

    def _note_shipped(self, srcs: Optional[np.ndarray],
                      offsets: Optional[np.ndarray], advance: int,
                      in_order: bool = True) -> None:
        """Advance the host insert-pointer mirror past one SUCCESSFUL ship
        of `advance` rows (ONE insert program; `in_order`: it handed
        ring_write no `offset`) and stamp the landed positions: `offsets` (row
        offsets from the pre-ship pointer) get `srcs`, everything else in
        the advanced range is marked untracked (-1) — other processes'
        interleave slots, padding."""
        start = (self._ptr_start + self._rows_landed) % self.capacity
        self._inserts_wrapped += start + advance > self.capacity
        self._insert_runs += self._plain_ring_write and run_fits(
            start, advance, self.capacity, in_order
        )
        self._rows_landed += advance
        if not self._track_sources:
            return
        pos_all = (self._host_ptr + np.arange(advance)) % self.capacity
        self._source_map[pos_all] = -1
        if srcs is not None and offsets is not None:
            pos = (self._host_ptr + offsets) % self.capacity
            self._source_map[pos] = srcs
        self._host_ptr = (self._host_ptr + advance) % self.capacity

    def sources_of(self, idx) -> np.ndarray:
        """Actor-slot ids that produced the rows at replay positions `idx`
        (-1 = untracked: sources off, another process's rows, restored
        contents, or padding). Best-effort under the async shipper — the
        map is stamped post-ship without a reader lock; attribution feeds
        a repeat-offender threshold, not an exact count."""
        idx = np.asarray(idx, np.int64)
        if self._source_map is None:
            return np.full(idx.shape, -1, np.int32)
        return self._source_map[idx % self.capacity]

    def _coalesce_k(self, n_blocks: int, cap_blocks: int, cap: Optional[int] = None) -> int:
        """Blocks to fold into the next super-block ship: largest power of
        two <= min(staged, coalesce cap, capacity) — capacity-capped so
        every scatter index within one super-block is distinct, which is
        what makes the coalesced scatter equal the serial sequence. The
        cap defaults to the static config value; single-process shipping
        paths pass the adaptive controller's effective cap (any cap
        sequence lands rows at identical positions, so adaptivity cannot
        perturb replay contents)."""
        k = min(n_blocks, cap or self._max_coalesce, max(1, cap_blocks))
        if k <= 0:
            return 0
        return 1 << (k.bit_length() - 1)

    def _effective_coalesce(self) -> int:
        return (
            self._adaptive.cap()
            if self._adaptive is not None
            else self._max_coalesce
        )

    def _drain_step(self) -> int:
        """Ship ONE coalesced super-block if at least one full block is
        staged; returns rows shipped. All pops happen under the dispatch
        lock so the pop -> device-op order is the ring's FIFO order no
        matter which thread ships (inline, _IngestShipper, or the transfer
        scheduler)."""
        cap_blocks = self.capacity // self.block_size
        with self.dispatch_lock:
            with self._staging:
                k = self._coalesce_k(
                    len(self._ring) // self.block_size, cap_blocks,
                    cap=self._effective_coalesce(),
                )
            if k == 0:
                return 0
            n = k * self.block_size
            # Pooled staging copy (transfer/hostbuf.py): acquire OUTSIDE
            # the staging condition (it may fence-wait on the device), pop
            # into it under the condition. The ring can only grow between
            # the two (every popper holds dispatch_lock), so k stays valid.
            buf = self._pool.acquire(n) if self._pool is not None else None
            with self._staging:
                rows = (
                    self._ring.pop_into(n, buf)
                    if buf is not None
                    else self._ring.pop(n)
                )
                srcs = self._pop_sources_locked(n)
                self._staging.notify_all()
            t0 = time.perf_counter()
            try:
                with trace.span("ingest_ship", rows=n, blocks=k):
                    self._ship(rows)
            except BaseException:
                if buf is not None:
                    # The ship never consumed the buffer into storage (or
                    # the orphaned device_put copy will never be read):
                    # return it unfenced so the bounded-restart resubmit
                    # does not find the pool drained.
                    self._pool.commit(buf, None)
                raise
            dt = time.perf_counter() - t0
            self._stats.record_ship(n, k, dt)
            # Source map advances only with a ship that actually landed —
            # like the device ptr, so the mirror can never drift on the
            # bounded-restart path (the popped rows AND their source tags
            # are lost together).
            self._note_shipped(srcs, None if srcs is None else np.arange(n), n)
            if buf is not None:
                # Fence on the insert's OUTPUT: the buffer recirculates
                # only after the op that read the transferred chunk has
                # executed (hostbuf.py module docstring).
                self._pool.commit(buf, self.size)
            if self._adaptive is not None:
                with self._staging:
                    queue_rows = len(self._ring)
                self._adaptive.observe_ship(k, dt, queue_rows)
        return n

    def _drain_ring(self) -> int:
        """Ship every currently-staged FULL block, coalesced. Called
        inline (sync mode), from the shipper thread (async mode), and from
        flush/sync_ship/drain_pending."""
        shipped = 0
        while True:
            n = self._drain_step()
            if n == 0:
                return shipped
            shipped += n

    # --- transfer-scheduler ingest work items (docs/TRANSFER.md) ---

    def _submit_ingest_locked(self) -> None:
        """Queue one ingest work item on the transfer scheduler if a full
        block is staged and none is in flight. Caller holds _staging."""
        if (
            not self._sched_ingest
            or self._ingest_inflight
            or len(self._ring) < self.block_size
        ):
            return
        self._ingest_inflight = True
        try:
            self._ingest_ticket = self._sched.submit(
                "ingest", self._scheduled_drain_step, label="ingest_ship"
            )
        except BaseException as e:
            # A dead/closed scheduler must not wedge ingest behind a
            # leaked in-flight flag, and must surface through the
            # contracted IngestError path (_check_shipper), not as a raw
            # TransferError from whoever happened to stage rows.
            self._ingest_inflight = False
            self._ingest_exc = self._ingest_exc or e

    def _scheduled_drain_step(self) -> int:
        """One scheduler-dispatched super-block ship. Re-arms itself while
        full blocks remain (one item in flight at a time, so the fair
        queue can interleave prefetch between super-blocks); failures park
        in _ingest_exc for the producer's bounded-restart check. Returns
        bytes moved (the scheduler's fair-queue currency)."""
        try:
            shipped = self._drain_step()
        except BaseException as e:
            with self._staging:
                self._ingest_inflight = False
                self._ingest_exc = e
                self._staging.notify_all()  # unblock backpressure waiters
            return 0
        with self._staging:
            self._ingest_inflight = False
            self._submit_ingest_locked()
        return shipped * self.width * 4

    def add_packed(self, block: np.ndarray, source: int = -1) -> None:
        """Stage packed [M, D] rows in the host ring; ship in fixed-size
        blocks (fixed power-of-two super-block shapes -> a bounded set of
        compiled inserts, no retrace churn). Multi-host: stages ONLY —
        rows leave via the lockstep sync_ship(). async_ship mode: the
        shipper thread does the device work; a full ring blocks here
        (backpressure, counted as ingest_stall_ms). `source` tags the
        rows' ingest source (actor slot) for the guardrails' bad-row
        attribution when track_sources is on; -1 = untracked."""
        self._check_shipper()
        rows = np.asarray(block, np.float32)
        stall = 0.0
        with self._staging:
            if self._async:
                t0 = time.perf_counter()
                while (
                    len(self._ring) + len(rows) > self._ring.capacity
                    and len(self._ring) >= self.block_size
                ):
                    self._staging.wait(0.05)
                    self._check_shipper()
                    if not self._async:
                        # close() raced us: nothing will drain the ring;
                        # fall through to push (the ring grows) and the
                        # inline ship below.
                        break
                stall = time.perf_counter() - t0
                if stall > 0.001:
                    # Producer blocked on a full staging ring: the
                    # backpressure interval as a span, so the timeline
                    # shows WHO was stalled while the shipper dispatched.
                    trace.complete(
                        "ingest_backpressure", t0, stall, rows=len(rows)
                    )
            self._ring.push(rows)
            if self._track_sources and len(rows):
                self._src_fifo.append([int(source), len(rows)])
            self._stats.record_push(len(rows), stall)
            self._staging.notify_all()
            self._submit_ingest_locked()
        if self._procs > 1 or self._async:
            return
        self._drain_ring()

    def insert_device_rows(self, rows) -> int:
        """Land an ALREADY-DEVICE-RESIDENT [M, D] block with the donated
        jitted insert — the device-actor path (actors/device_pool.py;
        docs/DEVICE_ACTORS.md). The rows never touch the host: no staging
        ring, no transfer-scheduler ingest class, no IngestStats traffic —
        the devactor_* family accounts for this source instead, and a
        device-actor-only run reports transfer_ingest_items == 0.

        Multi-host: `rows` must be REPLICATED (NamedSharding P(None, None))
        and every process must call this at the same loop point — the
        device-actor rollout is a global SPMD program all processes
        execute in lockstep, so the replicated storage cannot fork and the
        host-row sync_ship accounting is untouched. The source-map pointer
        mirror advances with untracked (-1) tags so host-row attribution
        (guardrails) stays aligned when both backends feed the ring."""
        m = int(rows.shape[0])
        if m == 0:
            return 0
        with self.dispatch_lock:
            old_ptr = self.ptr  # not donated by _insert; PER stamp input
            if self.sharded:
                if m % self._n_shards:
                    raise ValueError(
                        f"insert_device_rows: {m} rows do not divide over "
                        f"{self._n_shards} shards — sharded mode requires "
                        "every insert to move a multiple of the shard "
                        "count (keeps ptr N-aligned; config.py validates "
                        "the device-actor chunk shape when data_axis is "
                        "explicit)"
                    )
                self.storage, self.ptr, self.size = (
                    self._get_insert_replrows(m)(
                        self.storage, rows, self.ptr, self.size
                    )
                )
            else:
                self.storage, self.ptr, self.size = self._insert(
                    self.storage, rows, self.ptr, self.size
                )
            self._stamp_device_rows(m, old_ptr)
            self._note_shipped(None, None, m)
        return m

    def _stamp_device_rows(self, m: int, old_ptr) -> None:
        """PER hook: DevicePrioritizedReplay stamps the landed rows with
        the running max priority (every-transition-seen-once rule); the
        uniform buffer needs nothing."""

    def drain_pending(self) -> int:
        """Ship all staged full blocks and block until the inserts have
        executed — the barrier tests use before reading storage.
        Single-process only (multi-host draining IS sync_ship)."""
        if self._procs > 1:
            raise ReplayUsageError("drain_pending() is per-process; use "
                               "sync_ship() in multi-host runs")
        self._check_shipper()
        moved = self._drain_ring()
        with self.dispatch_lock:  # donation safety: see reward_sample
            jax.block_until_ready(self.storage)
        return moved

    def flush(self, min_rows: int = 1) -> None:
        """Force pending rows out (padded by repetition to the block shape —
        only used at warmup / shutdown, so the tiny duplication bias is
        confined to the first/last block). Single-process only; multi-host
        callers use sync_ship(force=True)."""
        if self._procs > 1:
            raise ReplayUsageError("flush() is per-process; use sync_ship() "
                               "in multi-host runs")
        self._check_shipper()
        self._drain_ring()
        with self.dispatch_lock:
            with self._staging:
                n = len(self._ring)
                rows = self._ring.pop(n) if (n >= min_rows and n > 0) else None
                srcs = (
                    self._pop_sources_locked(n) if rows is not None else None
                )
                if rows is not None:
                    self._staging.notify_all()
            if rows is not None:
                reps = -(-self.block_size // n)
                chunk = np.tile(rows, (reps, 1))[: self.block_size]
                t0 = time.perf_counter()
                with trace.span("ingest_flush", rows=n):
                    self._ship(chunk)
                self._stats.record_ship(n, 1, time.perf_counter() - t0)
                # Padding repeats real rows, so the copies inherit the
                # originals' source tags (a poisoned row's duplicate is
                # just as attributable).
                self._note_shipped(
                    None if srcs is None
                    else np.tile(srcs, reps)[: self.block_size],
                    np.arange(self.block_size),
                    self.block_size,
                )

    def sync_ship(self, force: bool = False) -> int:
        """Multi-host-safe ingest step. ALL processes must call this at the
        same point in their loop (train_jax: once per learner chunk) — it
        all-gathers pending counts and ships exactly min-over-processes
        full blocks, so every process executes the identical sequence of
        global device ops on a consistently-sharded block. Full blocks are
        coalesced into power-of-two super-blocks (identical k sequence on
        every process — it derives from the all-gathered min), each landed
        by ONE all-gathering insert whose on-device transpose reproduces
        the serial per-block interleave exactly.

        force=True additionally pads one block from the remainders (only
        when every process holds >= 1 pending row) — warmup/shutdown use.
        Returns locally shipped real (unpadded) rows. Single-process it
        degrades to the add_packed/flush fast path."""
        if self._procs == 1:
            self._check_shipper()
            moved = self._drain_ring()
            if force and self.pending_rows:
                moved += self.pending_rows
                self.flush()
            return moved
        if self._bg_sync:
            # Background-beat mode: even a synchronous caller must route
            # through the scheduler's lockstep lane — with beats possibly
            # queued ahead, a collective that bypassed the lane would
            # execute in a different order on different processes and
            # mismatch (docs/TRANSFER.md token protocol). The outer wait
            # is bounded by the CONFIGURED pod deadline (multihost.
            # wait_beat_ticket — a small multiple of
            # pod_collective_timeout_s plus any active grant), not a
            # hardcoded 10 minutes: a wedged lane surfaces as a typed
            # PodPeerLost on the clean-abort path (exit 76) instead of a
            # silent stall.
            from distributed_ddpg_tpu.parallel import multihost

            return multihost.wait_beat_ticket(
                self.sync_ship_begin(force=force)
            )
        return self._sync_ship_collective(force)

    def sync_ship_begin(self, force: bool = False):
        """Issue one lockstep ingest beat on the transfer scheduler's
        ordered lane and return its TransferTicket WITHOUT waiting — the
        background sync_ship mode (docs/TRANSFER.md). ALL processes must
        issue beats at the same points in the same order (train_jax's
        lockstep loop guarantees it), and the caller must wait the ticket
        before its next collective-bearing dispatch so per-process
        enqueue order stays identical. Each beat reads its pending count
        when it EXECUTES on the lane — strictly after every earlier beat
        (FIFO), so rows are never claimed twice; replicas agree because
        the shipped quantity derives from the all-gathered min, and the
        FIFO grouping invariance (_coalesce_k) keeps the final storage
        bit-identical to the synchronous reference."""
        if not self._bg_sync:
            raise ReplayUsageError(
                "sync_ship_begin() needs background_sync=True, an attached "
                "TransferScheduler, and a multi-process mesh"
            )
        self._beat += 1
        # Sharded beats ride the scheduler's shard_exchange class — the
        # SAME ordered lane (strict FIFO with lockstep, same pod deadline
        # wrap), separately accounted in transfer_shard_exchange_* so the
        # exchange cost is visible next to plain lockstep beats.
        return self._sched.submit(
            "shard_exchange" if self.sharded else "lockstep",
            lambda: self._sync_ship_collective(force),
            label=f"sync_ship_beat_{self._beat}",
        )

    def _sync_ship_collective(self, force: bool) -> int:
        # Count read at execution time (see sync_ship_begin): the staged
        # rows not consumed by any earlier beat. `count - moved` below is
        # stable against rows the producer stages concurrently — those
        # belong to a later beat.
        count = self.pending_rows
        from distributed_ddpg_tpu.parallel import multihost

        # Pod chaos trigger: the beat ordinal (see __init__). Fires
        # BEFORE the collective, so a kill/hang leaves the peers blocked
        # inside THIS beat's all-gather — the exact failure the pod
        # collective deadline (docs/RESILIENCE.md) exists to surface.
        if self._pod_fault is not None:
            self._pod_fault.tick()
        # One span over the whole lockstep beat (count all-gather +
        # ships): on the timeline this is the calling thread blocked on
        # the DCN collective — in background mode the span lands on the
        # transfer-sched track, overlapping the learner's chunk compute
        # (the overlap the ROADMAP lockstep-token item asked for).
        # beat_allgather piggybacks the pod heartbeat word on the count
        # payload (parallel/multihost.py peer-liveness tracking).
        with trace.span("sync_ship", beat=self._beat):
            counts = multihost.beat_allgather(count)
            m = int(counts.min())
            moved = 0
            cap_blocks = self.capacity // (self._procs * self.block_size)
            remaining = m // self.block_size
            with self.dispatch_lock:
                while remaining:
                    k = self._coalesce_k(remaining, cap_blocks)
                    with self._staging:
                        rows = self._ring.pop(k * self.block_size)
                        srcs = self._pop_sources_locked(k * self.block_size)
                    t0 = time.perf_counter()
                    with trace.span(
                        "ingest_ship_global", rows=k * self.block_size,
                        blocks=k,
                    ):
                        self._ship_global(rows, k=k)
                    self._stats.record_ship(
                        k * self.block_size, k, time.perf_counter() - t0
                    )
                    offsets = None
                    if srcs is not None:
                        # This process's k blocks land interleaved at
                        # offsets j*(procs*bs) + p*bs + r (the permuted
                        # scatter in _get_global_insert); other processes'
                        # slots stay -1 — each process attributes (and
                        # quarantines) only its own workers.
                        bs, procs, p = (
                            self.block_size, self._procs, self._proc_idx,
                        )
                        offsets = (
                            np.arange(k)[:, None] * (procs * bs)
                            + p * bs
                            + np.arange(bs)[None, :]
                        ).reshape(-1)
                    self._note_shipped(
                        srcs, offsets, self._procs * k * self.block_size,
                        in_order=k == 1,  # _get_global_insert's offset
                    )
                    moved += k * self.block_size
                    remaining -= k
                if force and m % self.block_size:
                    # Pad from the SNAPSHOT remainder (count was captured
                    # at token time): rows staged after the token belong
                    # to a later beat, and in background mode the producer
                    # may have staged more since.
                    take = min(count - moved, self.block_size)
                    with self._staging:
                        rows = self._ring.pop(take)
                        srcs = self._pop_sources_locked(take)
                    reps = -(-self.block_size // take)
                    t0 = time.perf_counter()
                    self._ship_global(
                        np.tile(rows, (reps, 1))[: self.block_size]
                    )
                    self._stats.record_ship(
                        take, 1, time.perf_counter() - t0
                    )
                    bs, procs, p = (
                        self.block_size, self._procs, self._proc_idx,
                    )
                    self._note_shipped(
                        None if srcs is None else np.tile(srcs, reps)[:bs],
                        p * bs + np.arange(bs),
                        procs * bs,
                    )
                    moved += take
        return moved

    # --- sharded placement (replay_sharding='sharded'; module docstring,
    # docs/REPLAY_SHARDING.md). Logical ring semantics are identical to
    # replicated mode; only WHERE each logical row physically lives
    # changes: position p -> shard p % N, local slot p // N. ---

    def _phys_of_logical(self, p) -> np.ndarray:
        """Physical storage row of logical ring position(s) p (host-side
        numpy; the device programs compute the same map inline)."""
        p = np.asarray(p, np.int64)
        return (p % self._n_shards) * self._shard_cap + p // self._n_shards

    def _to_logical_rows(self, phys: np.ndarray) -> np.ndarray:
        """Physical [capacity, ...] array -> logical ring order (the
        checkpoint wire format, shared with replicated mode so state_dicts
        roundtrip ACROSS placement modes)."""
        n, sc = self._n_shards, self._shard_cap
        return np.ascontiguousarray(
            phys.reshape(n, sc, *phys.shape[1:]).swapaxes(0, 1)
            .reshape(phys.shape)
        )

    def _to_physical_rows(self, logical: np.ndarray) -> np.ndarray:
        n, sc = self._n_shards, self._shard_cap
        return np.ascontiguousarray(
            logical.reshape(sc, n, *logical.shape[1:]).swapaxes(0, 1)
            .reshape(logical.shape)
        )

    def _get_insert_grouped(self, m: int):
        """Compiled sharded insert for an m-row staged ship whose host
        block was GROUPED by owner shard (_ship orders shard s's rows
        s-th): the sharded device_put lands each group on exactly its
        owner, and each shard scatters one contiguous local run — zero
        collective, 1/N landed bytes. Relies on ptr % N == 0 (module
        docstring invariant): group s's local slots all start at ptr // N.
        Cached per m (the same bounded power-of-two set as _insert)."""
        fn = self._insert_grouped_cache.get(m)
        if fn is None:
            from distributed_ddpg_tpu.parallel import mesh as mesh_lib

            n, sc, cap = self._n_shards, self._shard_cap, self.capacity

            def ring_insert_grouped(st, bl, ptr, size):
                start = ptr // n
                slots = (start + jnp.arange(m // n, dtype=jnp.int32)) % sc
                st = st.at[slots].set(bl)
                return st, (ptr + m) % cap, jnp.minimum(size + m, cap)

            fn = self.ring_program(jax.jit(
                mesh_lib.shard_map(
                    ring_insert_grouped, self._mesh,
                    in_specs=(P("data", None), P("data", None), P(), P()),
                    out_specs=(P("data", None), P(), P()),
                ),
                donate_argnums=(0,),
                in_shardings=(
                    self.storage_format, self._block_sharding_sharded,
                    self._scalar_sharding, self._scalar_sharding,
                ),
                out_shardings=(
                    self.storage_format, self._scalar_sharding,
                    self._scalar_sharding,
                ),
            ))
            self._insert_grouped_cache[m] = fn
        return fn

    def _make_insert_replrows_body(self, m: int):
        """Pure sharded insert for an m-row REPLICATED device block: every
        shard already holds the whole block, so each just gathers its
        owned rows (offset j with j % N == shard — ptr-aligned) and
        scatters them into its contiguous local run. No collective, no
        host bytes. Shared by the jitted standalone insert below and the
        fused-megastep composition (pure_insert_device_rows_fn)."""
        from distributed_ddpg_tpu.parallel import mesh as mesh_lib

        n, sc, cap = self._n_shards, self._shard_cap, self.capacity

        def ring_insert_device_rows(st, rows, ptr, size):
            s = jax.lax.axis_index("data")
            mine = rows[s + jnp.arange(m // n, dtype=jnp.int32) * n]
            start = ptr // n
            slots = (start + jnp.arange(m // n, dtype=jnp.int32)) % sc
            st = st.at[slots].set(mine)
            return st, (ptr + m) % cap, jnp.minimum(size + m, cap)

        return mesh_lib.shard_map(
            ring_insert_device_rows, self._mesh,
            in_specs=(P("data", None), P(), P(), P()),
            out_specs=(P("data", None), P(), P()),
        )

    def pure_insert_device_rows_fn(self, m: int):
        """Pure (unjitted) insert body for an m-row ALREADY-DEVICE-RESIDENT
        replicated block — (storage, rows, ptr, size) -> (storage, ptr,
        size) with the exact math insert_device_rows dispatches, for
        composition inside a larger jitted program (the fused megastep,
        parallel/megastep.py; docs/FUSED_BEAT.md). The caller owns
        donation and the host-side bookkeeping (note_device_rows)."""
        if not self.sharded:
            return self._insert_pure
        if m % self._n_shards:
            raise ReplayUsageError(
                f"pure_insert_device_rows_fn: {m} rows do not divide over "
                f"{self._n_shards} shards (the insert_device_rows "
                "alignment invariant)"
            )
        return self._make_insert_replrows_body(m)

    def note_device_rows(self, m: int, inserts: int = 1) -> None:
        """Advance the host-side source-attribution mirror past `inserts`
        in-program inserts of m device-produced rows each, landed by an
        EXTERNAL program (the fused megastep) — the same bookkeeping
        insert_device_rows does after its own insert. Caller holds
        dispatch_lock."""
        for _ in range(inserts):
            self._note_shipped(None, None, m)

    def _get_insert_replrows(self, m: int):
        """Compiled sharded insert for an m-row REPLICATED device block
        (the device-actor path, insert_device_rows): the jitted/donating
        wrapper over _make_insert_replrows_body."""
        fn = self._insert_replrows_cache.get(m)
        if fn is None:
            fn = self.ring_program(jax.jit(
                self._make_insert_replrows_body(m),
                donate_argnums=(0,),
                in_shardings=(
                    self.storage_format,
                    NamedSharding(self._mesh, P(None, None)),
                    self._scalar_sharding, self._scalar_sharding,
                ),
                out_shardings=(
                    self.storage_format, self._scalar_sharding,
                    self._scalar_sharding,
                ),
            ))
            self._insert_replrows_cache[m] = fn
        return fn

    def _make_reshard_body(self):
        """Pure restore-time reshard (elastic pod; docs/REPLAY_SHARDING.md
        all-writer checkpoints): the full LOGICAL ring arrives replicated
        (merged from a complete slice set, identical on every process),
        and each shard gathers exactly the positions it owns under THIS
        mesh's strided map (p % N) into its local run — the placement
        twin of _make_insert_replrows_body with no ring-pointer state.
        Because the input is placement-free logical order, the same
        program lands a slice set written by ANY process count M onto a
        pod of N processes (the N->M reshard). No collective, no host
        bytes beyond the replicated feed."""
        from distributed_ddpg_tpu.parallel import mesh as mesh_lib

        n, sc = self._n_shards, self._shard_cap

        def body(rows):
            s = jax.lax.axis_index("data")
            return rows[s + jnp.arange(sc, dtype=jnp.int32) * n]

        return mesh_lib.shard_map(
            body, self._mesh,
            in_specs=(P(None, None),),
            out_specs=P("data", None),
        )

    def _get_reshard(self):
        """Jitted _make_reshard_body — full-capacity logical rows
        (replicated) -> sharded physical storage. One program per buffer
        (restore-time only, never on the hot path)."""
        if not self.sharded:
            raise ReplayUsageError(
                "reshard is the sharded-placement restore program; "
                "replicated buffers load logical state directly"
            )
        fn = self._reshard_cache.get("rows")
        if fn is None:
            fn = self.ring_program(jax.jit(
                self._make_reshard_body(),
                in_shardings=(NamedSharding(self._mesh, P(None, None)),),
                out_shardings=self.storage_format,
            ))
            self._reshard_cache["rows"] = fn
        return fn

    def _get_global_insert_sharded(self, k: int):
        """Compiled multi-host sharded insert for a k-block lockstep beat:
        all-gather the process-major arrival block, compute each gathered
        row's logical target through the SAME per-process interleave math
        as the replicated path (_get_global_insert), and drop-scatter only
        the rows this shard owns into its local run. Per-device HBM writes
        and storage stay 1/N; the all-gather's wire bytes match the
        replicated beat (a true all-to-all lowering is the ROADMAP
        follow-on — gloo's CPU backend has no all_to_all to pin it
        against)."""
        fn = self._insert_global_sharded_cache.get(k)
        if fn is None:
            from distributed_ddpg_tpu.parallel import mesh as mesh_lib

            procs, bs = self._procs, self.block_size
            n, sc, cap = self._n_shards, self._shard_cap, self.capacity

            def ring_insert_global_sharded(st, bl, ptr, size):
                m = procs * k * bs
                full = jax.lax.all_gather(bl, "data", axis=0, tiled=True)
                g = jnp.arange(m, dtype=jnp.int32)
                if k > 1:
                    p = g // (k * bs)
                    j = (g % (k * bs)) // bs
                    r = g % bs
                    off = j * (procs * bs) + p * bs + r
                else:
                    off = g
                tgt = (ptr + off) % cap
                s = jax.lax.axis_index("data")
                loc = jnp.where((tgt % n) == s, tgt // n, sc)
                st = st.at[loc].set(full, mode="drop")
                return st, (ptr + m) % cap, jnp.minimum(size + m, cap)

            fn = self.ring_program(jax.jit(
                mesh_lib.shard_map(
                    ring_insert_global_sharded, self._mesh,
                    in_specs=(P("data", None), P("data", None), P(), P()),
                    out_specs=(P("data", None), P(), P()),
                ),
                donate_argnums=(0,),
                in_shardings=(
                    self.storage_format, self._block_sharding,
                    self._scalar_sharding, self._scalar_sharding,
                ),
                out_shardings=(
                    self.storage_format, self._scalar_sharding,
                    self._scalar_sharding,
                ),
            ))
            self._insert_global_sharded_cache[k] = fn
        return fn

    def _get_global_insert(self, k: int):
        """Compiled all-gathering insert for a k-block super-block. The
        global array arrives ordered [proc0's k blocks | proc1's k blocks
        | ...] (data-axis shard order); serial shipping would have landed
        it block-by-block as [b0p0 b0p1 ... | b1p0 b1p1 ...]. Rather than
        transposing the SHARDED operand (a resharding XLA's multiprocess
        CPU backend refuses to compile), the scatter INDICES are permuted:
        gathered row g = (p, j, r) writes at ptr + j*(procs*bs) + p*bs + r
        — pure elementwise iota math, same all-gather + local scatter
        structure as k=1, and the storage layout stays bit-identical to
        the seed's serial sequence. Cached per k (power-of-two set, so
        O(log max_coalesce) programs)."""
        fn = self._insert_global_cache.get(k)
        if fn is None:
            procs, bs = self._procs, self.block_size

            def ring_insert_global(storage, block, ptr, size):
                m = block.shape[0]  # procs * k * bs
                g = jnp.arange(m, dtype=jnp.int32)
                if k > 1:
                    p = g // (k * bs)
                    j = (g % (k * bs)) // bs
                    r = g % bs
                    offset = j * (procs * bs) + p * bs + r
                else:
                    offset = None
                storage = ring_write(storage, block, ptr, offset)
                new_ptr = (ptr + m) % self.capacity
                new_size = jnp.minimum(size + m, self.capacity)
                return storage, new_ptr, new_size

            fn = self.ring_program(jax.jit(
                ring_insert_global,
                donate_argnums=(0,),
                in_shardings=self._global_in_shardings,
                out_shardings=self._global_out_shardings,
            ))
            self._insert_global_cache[k] = fn
        return fn

    def _ship_global(self, local_rows: np.ndarray, k: int = 1) -> None:
        if self._fault is not None:
            self._fault.tick()
        t0 = time.perf_counter()
        block = jax.make_array_from_process_local_data(
            self._block_sharding,
            np.ascontiguousarray(local_rows, np.float32),
            (self._procs * k * self.block_size, self.width),
        )
        insert = (
            self._get_global_insert_sharded(k)
            if self.sharded
            else self._get_global_insert(k)
        )
        self.storage, self.ptr, self.size = insert(
            self.storage, block, self.ptr, self.size
        )
        # This process's h2d contribution (its own local rows, once); the
        # collective's cross-device traffic is not host-visible here.
        self._shard_stats.record_ship(
            self._procs * k * self.block_size,
            sum(s.data.nbytes for s in block.addressable_shards),
            time.perf_counter() - t0,
        )

    def _ship(self, chunk: np.ndarray) -> None:
        if self._fault is not None:
            self._fault.tick()
        t0 = time.perf_counter()
        m = len(chunk)
        if self.sharded:
            # Group rows by owner shard (owner of ptr+j is j % N — ptr is
            # N-aligned) so the sharded device_put lands each row ONLY on
            # its owner: 1/N of the replicated path's landed bytes
            # (counted by ReplayShardStats: replay_ingest_bytes_per_row).
            n = self._n_shards
            grouped = np.ascontiguousarray(
                np.asarray(chunk, np.float32)
                .reshape(m // n, n, self.width)
                .transpose(1, 0, 2)
                .reshape(m, self.width)
            )
            block = jax.device_put(grouped, self._block_sharding_sharded)
            nbytes = sum(s.data.nbytes for s in block.addressable_shards)
            self.storage, self.ptr, self.size = self._get_insert_grouped(m)(
                self.storage, block, self.ptr, self.size
            )
        else:
            if self._mesh is not None:
                chunk = jax.device_put(
                    chunk, NamedSharding(self._mesh, P(None, None))
                )
                nbytes = sum(
                    s.data.nbytes for s in chunk.addressable_shards
                )
            else:
                nbytes = m * self.width * 4
            self.storage, self.ptr, self.size = self._insert(
                self.storage, chunk, self.ptr, self.size
            )
        self._shard_stats.record_ship(m, nbytes, time.perf_counter() - t0)

    # --- state for the fused sampling learner path ---

    def device_state(self):
        return self.storage, self.size

    def ring_program(self, jitted):
        """`jitted`, a program whose output names storage_format, made safe
        to dispatch: behind _RingProgram where the ring is held row-major,
        itself otherwise."""
        if isinstance(self.storage_format, Format):
            return _RingProgram(jitted)
        return jitted

    # --- checkpoint support (same contract as host buffers) ---

    def state_dict(self):
        with self.dispatch_lock:
            if self.sharded and self._procs > 1:
                raise ReplayUsageError(
                    "sharded replay contents span processes and have no "
                    "single-writer snapshot; each process checkpoints its "
                    "own slice instead (slice_state_dict + "
                    "checkpoint.write_replay_slice; docs/REPLAY_SHARDING.md)"
                )
            n = len(self)
            storage = np.asarray(jax.device_get(self.storage))
            if self.sharded:
                # Checkpoint wire format is LOGICAL ring order — shared
                # with replicated mode, so state_dicts roundtrip across
                # placement modes.
                storage = self._to_logical_rows(storage)
            return {
                "packed": storage[:n].copy(),
                "ptr": np.asarray(int(jax.device_get(self.ptr))),
                "size": np.asarray(n),
            }

    def slice_state_dict(self):
        """This process's slice of the logical ring — the all-writer
        checkpoint payload (checkpoint.write_replay_slice;
        docs/REPLAY_SHARDING.md). `positions` are the LOGICAL ring indices
        in [0, size) whose shards this process hosts (strided ownership
        p % N), ascending; `rows` are the packed rows at those positions.
        The format is position-indexed rather than shard-indexed, so a
        restore can merge any complete set and re-scatter to a DIFFERENT
        process count (merge_slice_states + load_state_dict). A
        single-process buffer (replicated or sharded) degenerates to one
        slice covering the whole ring."""
        with self.dispatch_lock:
            if not (self.sharded and self._procs > 1):
                st = self.state_dict()
                n = int(st["size"])
                out = {
                    "positions": np.arange(n, dtype=np.int64),
                    "rows": np.asarray(st["packed"], np.float32),
                    "ptr": np.asarray(int(st["ptr"]), np.int64),
                    "size": np.asarray(n, np.int64),
                    "capacity": np.asarray(self.capacity, np.int64),
                }
                if "priorities" in st:
                    out["priorities"] = np.asarray(
                        st["priorities"], np.float32
                    )
                    out["max_priority"] = np.asarray(
                        st["max_priority"], np.float32
                    )
                return out
            n = int(jax.device_get(self.size))
            ptr = int(jax.device_get(self.ptr))
            N, sc = self._n_shards, self._shard_cap
            pos_parts, row_parts = [], []
            seen = set()
            for sh in self.storage.addressable_shards:
                # Model-axis replicas repeat the same data shard; dedupe
                # by the shard's row offset into the global array.
                start = sh.index[0].start or 0
                if start in seen:
                    continue
                seen.add(start)
                sid = start // sc
                cnt = (n - sid + N - 1) // N if n > sid else 0
                if cnt <= 0:
                    continue
                # Local slot j of shard sid holds logical sid + j*N.
                pos_parts.append(
                    sid + np.arange(cnt, dtype=np.int64) * N
                )
                row_parts.append(
                    np.asarray(np.asarray(sh.data)[:cnt], np.float32)
                )
            if pos_parts:
                positions = np.concatenate(pos_parts)
                rows = np.concatenate(row_parts)
                order = np.argsort(positions, kind="stable")
                positions = positions[order]
                rows = np.ascontiguousarray(rows[order])
            else:
                positions = np.zeros((0,), np.int64)
                rows = np.zeros((0, self.width), np.float32)
            return {
                "positions": positions,
                "rows": rows,
                "ptr": np.asarray(ptr, np.int64),
                "size": np.asarray(n, np.int64),
                "capacity": np.asarray(self.capacity, np.int64),
            }

    def _replicated_scalar(self, v: int):
        out = jnp.asarray(int(v), jnp.int32)
        if self._mesh is not None:
            out = jax.device_put(out, NamedSharding(self._mesh, P()))
        return out

    def _load_state_multihost(self, state) -> None:
        """Multi-host sharded restore (elastic pod): every process holds
        the SAME full logical state (merged from a verified slice set on
        the shared checkpoint namespace), feeds it replicated — the
        module-docstring device_put discipline: identical global value on
        every process — and the reshard program scatters each shard's
        owned positions locally. This is the N->M reshard: the slice
        set's writer count never appears here, only the logical order."""
        n = int(state["size"])
        with self.dispatch_lock:
            full = np.zeros((self.capacity, self.width), np.float32)
            full[:n] = np.asarray(state["packed"], np.float32)
            rows = jax.device_put(
                jnp.asarray(full), NamedSharding(self._mesh, P(None, None))
            )
            self.storage = self._get_reshard()(rows)
            self.ptr = self._replicated_scalar(
                int(state["ptr"]) % self.capacity
            )
            self.size = self._replicated_scalar(n)
            self._ptr_start = int(state["ptr"]) % self.capacity
            self._rows_landed = 0
            if self._track_sources:
                self._source_map.fill(-1)
                self._src_fifo.clear()
                self._host_ptr = int(state["ptr"]) % self.capacity

    def load_state_dict(self, state) -> None:
        n = int(state["size"])
        if n > self.capacity:
            raise ValueError(f"checkpointed size {n} exceeds capacity {self.capacity}")
        if self.sharded and self._procs > 1:
            self._load_state_multihost(state)
            return
        with self.dispatch_lock:
            if self.sharded:
                # np.array: device_get hands back a READ-ONLY buffer, and
                # the logical permutation is a no-op (same buffer) when
                # there is a single shard.
                storage = self._to_logical_rows(
                    np.array(jax.device_get(self.storage))
                )
                storage[:n] = state["packed"]
                storage = self._to_physical_rows(storage)
            else:
                storage = np.array(jax.device_get(self.storage))  # writable copy
                storage[:n] = state["packed"]
            self.storage = self._place_storage(storage)
            self.ptr = jnp.asarray(int(state["ptr"]) % self.capacity, jnp.int32)
            self.size = jnp.asarray(n, jnp.int32)
            if self._mesh is not None:
                scalar = NamedSharding(self._mesh, P())
                self.ptr = jax.device_put(self.ptr, scalar)
                self.size = jax.device_put(self.size, scalar)
            self._ptr_start = int(state["ptr"]) % self.capacity
            self._rows_landed = 0
            if self._track_sources:
                # Restored rows carry no attribution; re-sync the pointer
                # mirror with the restored device ptr.
                self._source_map.fill(-1)
                self._src_fifo.clear()
                self._host_ptr = int(state["ptr"]) % self.capacity


def merge_slice_states(slices):
    """Merge a complete all-writer slice set (checkpoint.load_replay_slices
    output, any order) back into ONE logical-order state_dict —
    load_state_dict's wire format, placement-portable by construction.
    Validates that every slice agrees on the ring scalars and that the
    positions tile [0, size) exactly once: a hole or an overlap means the
    set mixes worlds or writers, and silently loading it would corrupt the
    data distribution the learner resumes on."""
    if not slices:
        raise ReplayUsageError("merge_slice_states: empty slice set")
    size = int(slices[0]["size"])
    ptr = int(slices[0]["ptr"])
    cap = int(slices[0]["capacity"])
    for s in slices:
        got = (int(s["size"]), int(s["ptr"]), int(s["capacity"]))
        if got != (size, ptr, cap):
            raise ReplayUsageError(
                f"slice set disagrees on ring scalars: {got} != "
                f"{(size, ptr, cap)} (slices from different steps or runs)"
            )
    width = int(np.asarray(slices[0]["rows"]).shape[-1])
    packed = np.zeros((size, width), np.float32)
    covered = np.zeros(size, bool)
    has_prio = any("priorities" in s for s in slices)
    prios = np.zeros(size, np.float32) if has_prio else None
    maxp = 1.0
    for s in slices:
        pos = np.asarray(s["positions"], np.int64)
        if pos.size == 0:
            continue
        if pos.min() < 0 or pos.max() >= size:
            raise ReplayUsageError(
                f"slice positions out of range [0, {size}): "
                f"[{pos.min()}, {pos.max()}]"
            )
        if covered[pos].any():
            raise ReplayUsageError(
                "overlapping slice positions (two writers claim the same "
                "ring rows — mixed slice sets)"
            )
        packed[pos] = np.asarray(s["rows"], np.float32)
        covered[pos] = True
        if has_prio:
            prios[pos] = np.asarray(s["priorities"], np.float32)
            maxp = max(maxp, float(s["max_priority"]))
    if not covered.all():
        raise ReplayUsageError(
            f"slice set does not cover the ring: {int((~covered).sum())} "
            f"of {size} positions missing"
        )
    out = {
        "packed": packed,
        "ptr": np.asarray(ptr),
        "size": np.asarray(size),
    }
    if has_prio:
        out["priorities"] = prios
        out["max_priority"] = np.asarray(maxp, np.float32)
    return out


def split_slice_state(state, nslices: int, capacity: int):
    """Partition a full logical state_dict into `nslices` position-strided
    slices (position p -> slice p % n, the ownership map an n-process
    sharded pod would have written) — the inverse of merge_slice_states,
    for the reshard-matrix tests and offline resharding tools."""
    n = int(state["size"])
    out = []
    for k in range(nslices):
        pos = np.arange(k, n, nslices, dtype=np.int64)
        sl = {
            "positions": pos,
            "rows": np.asarray(state["packed"], np.float32)[pos],
            "ptr": np.asarray(int(state["ptr"]), np.int64),
            "size": np.asarray(n, np.int64),
            "capacity": np.asarray(int(capacity), np.int64),
        }
        if "priorities" in state:
            sl["priorities"] = np.asarray(
                state["priorities"], np.float32
            )[pos]
            sl["max_priority"] = np.asarray(
                state["max_priority"], np.float32
            )
        out.append(sl)
    return out


def draw_per_indices(key, priorities, size, shape, beta):
    """Stratified proportional PER draw, fully on device (the TPU-native
    replacement for the host sum-tree walk, replay/prioritized.py): one
    cumsum over the priority vector + a vectorized searchsorted — O(cap)
    memory-bandwidth + O(n log cap) compare ops, no branchy tree descent.

    shape = (K, B): K scan steps of B samples, stratified within each B
    (mirroring SumTree.stratified_sample). Returns (idx[K,B], weights[K,B])
    with IS weights w = (size * p/total)^-beta normalized per B-batch by
    its max (exactly the host formula).

    f32 cumsum note: with ~1e6 priorities the running total's f32 ulp is
    ~0.06 at total ~1e6, so individual sample boundaries can shift by
    O(ulp/total) probability mass — negligible against PER's own eps floor;
    the host tree keeps f64 and the parity test bounds the difference."""
    k, b = shape
    cum = jnp.cumsum(priorities)
    total = cum[-1]
    u = (jnp.arange(b, dtype=jnp.float32)[None, :]
         + jax.random.uniform(key, (k, b))) / b * total
    idx = jnp.searchsorted(cum, u.reshape(-1), side="right").reshape(k, b)
    idx = jnp.minimum(idx.astype(jnp.int32), jnp.maximum(size - 1, 0))
    probs = priorities[idx] / jnp.maximum(total, 1e-12)
    weights = (size.astype(jnp.float32) * jnp.maximum(probs, 1e-12)) ** (-beta)
    weights = weights / jnp.max(weights, axis=-1, keepdims=True)
    return idx, weights


def make_sharded_per_draw(mesh):
    """Factory for the SHARDED counterpart of draw_per_indices: shard-
    local priority cumsums with a replicated top-level sampler
    (docs/REPLAY_SHARDING.md; the 'shard-local trees, replicated root'
    shape replay/prioritized.py's host sum-tree hints at). Each shard
    cumsums only its own priority slots; the per-shard masses are
    all-gathered (N floats — the tiny 'root node' exchange); the
    stratified uniforms are drawn replica-identically from the same key
    and each lands in exactly one shard's half-open mass interval
    (interval bounds come from ONE replicated cumsum of the gathered
    totals, so no f32 reassociation can double- or zero-claim a sample;
    the last shard's upper bound is +inf to absorb u==total rounding).
    The owning shard searches its local cumsum and contributes the
    LOGICAL index + priority; a psum (each sample has exactly one
    contributor) replicates them. Same signature and weight formula as
    draw_per_indices; the sampling distribution matches, the exact index
    stream does not (different cumsum partition), so the sharded-PER test
    is statistical where the uniform parity oracle is exact."""
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib

    n = mesh.shape["data"]

    def draw(key, priorities, size, shape, beta):
        k, b = shape

        def body(key, pr, size):
            sc = pr.shape[0]
            s = jax.lax.axis_index("data")
            cum = jnp.cumsum(pr)
            totals = jax.lax.all_gather(cum[-1], "data")
            cumtot = jnp.cumsum(totals)
            total = cumtot[-1]
            lo = jnp.where(s == 0, 0.0, cumtot[jnp.maximum(s - 1, 0)])
            hi = jnp.where(s == n - 1, jnp.inf, cumtot[s])
            u = (
                jnp.arange(b, dtype=jnp.float32)[None, :]
                + jax.random.uniform(key, (k, b))
            ) / b * total
            mine = (u >= lo) & (u < hi)
            loc = jnp.searchsorted(
                cum, (u - lo).reshape(-1), side="right"
            ).reshape(k, b)
            # Clamp to this shard's last LIVE slot, not its capacity: a
            # boundary-rounded u (fl(lo + tot) can exceed lo + cum[-1] by
            # an ulp, and u == total can reach the last shard) would
            # otherwise searchsort past the live region and select an
            # empty zero-priority slot — idx >= size with probs == 0,
            # whose (size * 1e-12)^-beta IS weight would crush the whole
            # batch's normalization. The live bound keeps the gathered
            # priority consistent with the returned index — the sharded
            # twin of draw_per_indices' jnp.minimum(idx, size - 1). A
            # shard with zero live rows has tot == 0 and never claims, so
            # the maximum(., 1) floor is never observable.
            live = jnp.maximum((size - s + n - 1) // n, 1)
            loc = jnp.minimum(
                loc.astype(jnp.int32), jnp.minimum(live - 1, sc - 1)
            )
            idx = jax.lax.psum(jnp.where(mine, loc * n + s, 0), "data")
            p = jax.lax.psum(jnp.where(mine, pr[loc], 0.0), "data")
            return idx, p, total

        idx, probs_raw, total = mesh_lib.shard_map(
            body, mesh,
            in_specs=(P(), P("data"), P()), out_specs=(P(), P(), P()),
        )(key, priorities, size)
        probs = probs_raw / jnp.maximum(total, 1e-12)
        weights = (
            size.astype(jnp.float32) * jnp.maximum(probs, 1e-12)
        ) ** (-beta)
        weights = weights / jnp.max(weights, axis=-1, keepdims=True)
        return idx, weights

    return draw


class DevicePrioritizedReplay(DeviceReplay):
    """Proportional PER with priorities resident in HBM (SURVEY.md §7 hard
    part (a) applied to PER; VERDICT.md round-1 Missing #4).

    The host PrioritizedReplay keeps a sum-tree on CPU, which forces the
    flagship path back to host sampling + per-chunk h2d transfers. Here the
    priority vector is a replicated f32[capacity] device array:

      - inserts stamp new rows with the running max priority (same
        every-transition-seen-once rule as the host buffer) inside a jitted
        scatter chained onto the storage insert;
      - sampling is draw_per_indices fused INTO the learner chunk
        (ShardedLearner.run_sample_chunk on a prioritized replay) — zero
        h2d, zero d2h for priorities;
      - priority updates scatter (|td|+eps)^alpha for the chunk's sampled
        indices at chunk end — the same once-per-chunk cadence the host
        path has (update_priorities is called once per after_chunk).

    Coalesced ingest stamps the whole super-block from the pre-insert ptr
    with the current max priority — exactly what k serial stamps with the
    same (learner-updated-only) max would do, so parity holds.

    Multi-host: priorities/max_priority are replicated like storage, and
    every update is computed from replicated inputs (state, key, td), so
    replicas stay identical with no extra collectives."""

    def __init__(
        self,
        capacity: int,
        obs_dim: int,
        act_dim: int,
        mesh: Optional[Mesh] = None,
        block_size: int = 4096,
        seed: int = 0,
        alpha: float = 0.6,
        eps: float = 1e-6,
        **kwargs,
    ):
        super().__init__(capacity, obs_dim, act_dim, mesh=mesh,
                         block_size=block_size, seed=seed, **kwargs)
        self.alpha = float(alpha)
        self.eps = float(eps)
        # Sharded mode: priorities shard over 'data' with the SAME strided
        # placement as storage (logical slot p -> shard p % N), so the
        # scatter/stamp index math is shared and the two arrays can never
        # disagree about a row's owner.
        vec_sharding = (
            NamedSharding(mesh, P("data") if self.sharded else P(None))
            if mesh is not None
            else None
        )
        scalar_sharding = NamedSharding(mesh, P()) if mesh is not None else None
        self._stamp_shardings = (vec_sharding, scalar_sharding)
        self.priorities = jnp.zeros((self.capacity,), jnp.float32)
        self.max_priority = jnp.ones((), jnp.float32)
        if vec_sharding is not None:
            self.priorities = jax.device_put(self.priorities, vec_sharding)
            self.max_priority = jax.device_put(self.max_priority, scalar_sharding)
        # One stamp program per super-block row count m (power-of-two
        # multiples of block_size, same bounded set as the inserts).
        self._stamp_cache = {}

    def _make_stamp_body(self, m: int):
        """Pure stamp body — (priorities, maxp, old_ptr) -> priorities —
        shared by the jitted standalone stamp and the fused-megastep
        composition (pure_stamp_fn)."""
        if self.sharded:
            # Sharded stamp: the landed positions are a contiguous
            # logical run starting at the N-aligned old_ptr, so each
            # shard stamps its own contiguous m/N local slots — the
            # priority twin of _get_insert_grouped, no collective.
            from distributed_ddpg_tpu.parallel import mesh as mesh_lib

            n, sc = self._n_shards, self._shard_cap

            def ring_insert_stamp(prios, maxp, old_ptr):
                start = old_ptr // n
                slots = (
                    start + jnp.arange(m // n, dtype=jnp.int32)
                ) % sc
                return prios.at[slots].set(maxp)

            return mesh_lib.shard_map(
                ring_insert_stamp, self._mesh,
                in_specs=(P("data"), P(), P()),
                out_specs=P("data"),
            )

        def ring_insert_stamp(prios, maxp, old_ptr):
            idx = (old_ptr + jnp.arange(m, dtype=jnp.int32)) % self.capacity
            return prios.at[idx].set(maxp)

        return ring_insert_stamp

    def pure_stamp_fn(self, m: int):
        """Pure (unjitted) max-priority stamp for m freshly-landed rows,
        for composition inside a larger jitted program (the fused
        megastep's in-program insert stamps exactly like
        _stamp_device_rows would after a standalone one)."""
        return self._make_stamp_body(m)

    def _get_stamp(self, m: int):
        fn = self._stamp_cache.get(m)
        if fn is None:
            vec_sharding, scalar_sharding = self._stamp_shardings
            kwargs = (
                dict(
                    in_shardings=(vec_sharding, scalar_sharding, scalar_sharding),
                    out_shardings=vec_sharding,
                )
                if vec_sharding is not None
                else {}
            )
            fn = jax.jit(
                self._make_stamp_body(m), donate_argnums=(0,), **kwargs
            )
            self._stamp_cache[m] = fn
        return fn

    def _ship(self, chunk: np.ndarray) -> None:
        old_ptr = self.ptr  # not donated by _insert; still valid after
        super()._ship(chunk)
        self.priorities = self._get_stamp(len(chunk))(
            self.priorities, self.max_priority, old_ptr
        )

    def _ship_global(self, local_rows: np.ndarray, k: int = 1) -> None:
        old_ptr = self.ptr
        super()._ship_global(local_rows, k=k)
        self.priorities = self._get_stamp(self._procs * k * self.block_size)(
            self.priorities, self.max_priority, old_ptr
        )

    def _stamp_device_rows(self, m: int, old_ptr) -> None:
        # Device-actor inserts (insert_device_rows) stamp like every other
        # source: the running max priority over the landed range, from the
        # pre-insert pointer.
        self.priorities = self._get_stamp(m)(
            self.priorities, self.max_priority, old_ptr
        )

    # --- state for the fused PER sampling learner path ---

    def per_state(self):
        return self.storage, self.size, self.priorities, self.max_priority

    def set_per_state(self, priorities, max_priority) -> None:
        """Install the updated priority vector returned by the learner's
        fused chunk (both already carry the replicated sharding). Callers
        must hold dispatch_lock across per_state -> dispatch ->
        set_per_state (parallel/learner.py does) — otherwise a concurrent
        shipper stamp between the read and this write would be lost and
        freshly-inserted rows would keep priority 0 forever."""
        self.priorities = priorities
        self.max_priority = max_priority

    # --- checkpoint support ---

    def _get_prio_reshard(self):
        """Jitted restore-time reshard for the priority vector — the 1-D
        twin of _get_reshard, sharing the strided ownership map so the
        priorities can never land on a different owner than their rows
        (the rebuild half of 'priority-tree rebuild': shard-local
        cumsums are recomputed from these slots at the next draw)."""
        if not self.sharded:
            raise ReplayUsageError(
                "prio reshard is the sharded-placement restore program"
            )
        fn = self._reshard_cache.get("prio")
        if fn is None:
            from distributed_ddpg_tpu.parallel import mesh as mesh_lib

            n, sc = self._n_shards, self._shard_cap

            def body(prios):
                s = jax.lax.axis_index("data")
                return prios[s + jnp.arange(sc, dtype=jnp.int32) * n]

            fn = jax.jit(
                mesh_lib.shard_map(
                    body, self._mesh, in_specs=(P(None),), out_specs=P("data")
                ),
                in_shardings=(NamedSharding(self._mesh, P(None)),),
                out_shardings=self._stamp_shardings[0],
            )
            self._reshard_cache["prio"] = fn
        return fn

    def state_dict(self):
        with self.dispatch_lock:
            state = super().state_dict()
            n = int(state["size"])
            prios = np.asarray(jax.device_get(self.priorities))
            if self.sharded:
                prios = self._to_logical_rows(prios)
            state["priorities"] = prios[:n].copy()
            state["max_priority"] = np.asarray(
                float(jax.device_get(self.max_priority))
            )
            return state

    def slice_state_dict(self):
        with self.dispatch_lock:
            out = super().slice_state_dict()
            if not (self.sharded and self._procs > 1):
                return out  # state_dict already carried the priorities
            n = int(out["size"])
            N, sc = self._n_shards, self._shard_cap
            # Priorities share the rows' strided owner map, so the slots
            # backing out["positions"] live in this process's priority
            # shards; index them through a position-keyed scratch vector
            # to reuse the base class's position ordering.
            scratch = np.zeros(self.capacity, np.float32)
            seen = set()
            for sh in self.priorities.addressable_shards:
                start = sh.index[0].start or 0
                if start in seen:
                    continue
                seen.add(start)
                sid = start // sc
                cnt = (n - sid + N - 1) // N if n > sid else 0
                if cnt <= 0:
                    continue
                scratch[sid + np.arange(cnt, dtype=np.int64) * N] = (
                    np.asarray(sh.data)[:cnt]
                )
            out["priorities"] = scratch[out["positions"]]
            out["max_priority"] = np.asarray(
                float(jax.device_get(self.max_priority)), np.float32
            )
            return out

    def load_state_dict(self, state) -> None:
        with self.dispatch_lock:
            super().load_state_dict(state)
            if "priorities" not in state:
                return
            n = int(state["size"])
            if self.sharded and self._procs > 1:
                # Elastic restore (the _load_state_multihost twin): feed
                # the full logical priority vector replicated, scatter
                # each shard's owned slots locally.
                full = np.zeros((self.capacity,), np.float32)
                full[:n] = np.asarray(state["priorities"], np.float32)
                rep = jax.device_put(
                    jnp.asarray(full), NamedSharding(self._mesh, P(None))
                )
                self.priorities = self._get_prio_reshard()(rep)
                self.max_priority = jax.device_put(
                    jnp.asarray(float(state["max_priority"]), jnp.float32),
                    self._stamp_shardings[1],
                )
                return
            prios = np.array(jax.device_get(self.priorities))
            if self.sharded:
                prios = self._to_logical_rows(prios)
            prios[:n] = state["priorities"]
            if self.sharded:
                prios = self._to_physical_rows(prios)
            vec_sharding = self._stamp_shardings[0]
            scalar = (
                NamedSharding(self._mesh, P()) if self._mesh is not None else None
            )
            self.priorities = jnp.asarray(prios)
            self.max_priority = jnp.asarray(
                float(state["max_priority"]), jnp.float32
            )
            if vec_sharding is not None:
                self.priorities = jax.device_put(self.priorities, vec_sharding)
                self.max_priority = jax.device_put(self.max_priority, scalar)


# ---------------------------------------------------------------------------
# program-contract analyzer hook (analysis/programs.py; docs/ANALYSIS.md
# "Layer 2")
# ---------------------------------------------------------------------------


def program_specs():
    """The donated insert/scatter/stamp program family, built over tiny
    rings (capacity 64, blocks of 8) — replicated (compact rows at width
    65, packed lines at width 10) and sharded placement. The multi-host
    global inserts (all-gather beats) need a real
    multi-process pod and are exercised by the gloo chaos tests instead;
    this registry holds what one process can trace."""
    from distributed_ddpg_tpu.analysis.programs import (
        BuiltProgram,
        ProgramSpec,
        probe_mesh,
    )

    OWNER = "replay/device.py"
    M = 8  # rows per probe ship (one block)

    def insert(obs_dim, layout):
        def build():
            r = DeviceReplay(64, obs_dim, 1, block_size=M, async_ship=False)
            assert r.ring_layout == layout
            block = np.zeros((M, r.width), np.float32)
            return BuiltProgram(
                r._insert, (r.storage, block, r.ptr, r.size), (0,)
            )

        return build

    def insert_sharded():
        r = DeviceReplay(
            64, 3, 1, mesh=probe_mesh(), block_size=M, async_ship=False,
            replay_sharding="sharded",
        )
        block = jax.device_put(
            np.zeros((M, r.width), np.float32), r._block_sharding_sharded
        )
        return BuiltProgram(
            r._get_insert_grouped(M), (r.storage, block, r.ptr, r.size), (0,)
        )

    def insert_devrows_sharded():
        mesh = probe_mesh()
        r = DeviceReplay(
            64, 3, 1, mesh=mesh, block_size=M, async_ship=False,
            replay_sharding="sharded",
        )
        rows = jax.device_put(
            np.zeros((M, r.width), np.float32),
            NamedSharding(mesh, P(None, None)),
        )
        return BuiltProgram(
            r._get_insert_replrows(M), (r.storage, rows, r.ptr, r.size), (0,)
        )

    def stamp():
        r = DevicePrioritizedReplay(64, 3, 1, block_size=M, async_ship=False)
        return BuiltProgram(
            r._get_stamp(M), (r.priorities, r.max_priority, r.ptr), (0,)
        )

    def stamp_sharded():
        r = DevicePrioritizedReplay(
            64, 3, 1, mesh=probe_mesh(), block_size=M, async_ship=False,
            replay_sharding="sharded",
        )
        return BuiltProgram(
            r._get_stamp(M), (r.priorities, r.max_priority, r.ptr), (0,)
        )

    def reshard_sharded():
        # The elastic-pod restore scatter (docs/REPLAY_SHARDING.md
        # all-writer checkpoints): full logical ring replicated -> each
        # shard's owned positions. Not donated — restore-time only, and
        # the replicated input never aliases the sharded output.
        r = DeviceReplay(
            64, 3, 1, mesh=probe_mesh(), block_size=M, async_ship=False,
            replay_sharding="sharded",
        )
        rows = jax.device_put(
            np.zeros((64, r.width), np.float32),
            NamedSharding(r._mesh, P(None, None)),
        )
        return BuiltProgram(r._get_reshard(), (rows,), ())

    def per_reshard_sharded():
        r = DevicePrioritizedReplay(
            64, 3, 1, mesh=probe_mesh(), block_size=M, async_ship=False,
            replay_sharding="sharded",
        )
        prios = jax.device_put(
            np.zeros((64,), np.float32), NamedSharding(r._mesh, P(None))
        )
        return BuiltProgram(r._get_prio_reshard(), (prios,), ())

    return [
        ProgramSpec("replay.insert", OWNER, insert(31, "compact")),
        ProgramSpec("replay.insert.packed", OWNER, insert(3, "packed")),
        ProgramSpec("replay.insert.sharded", OWNER, insert_sharded),
        ProgramSpec(
            "replay.insert.devrows.sharded", OWNER, insert_devrows_sharded
        ),
        ProgramSpec("replay.stamp", OWNER, stamp),
        ProgramSpec("replay.stamp.sharded", OWNER, stamp_sharded),
        ProgramSpec("replay.reshard.sharded", OWNER, reshard_sharded),
        ProgramSpec("replay.per.reshard.sharded", OWNER, per_reshard_sharded),
    ]
