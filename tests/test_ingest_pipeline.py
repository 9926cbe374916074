"""Ingest pipeline tests (docs/INGEST.md).

The coalesced / async host->HBM replay ingest must be BIT-IDENTICAL to the
seed's serial block-at-a-time shipping for the same inflow — storage, ptr,
size (and PER priorities) — including the flush() padding block. Plus: the
host staging ring's FIFO/wrap/growth behavior, backpressure + observability
surface, shipper-death surfacing and ChunkPrefetcher stop hardening.
"""

import threading
import time

import jax
import numpy as np
import pytest

from distributed_ddpg_tpu.parallel.mesh import make_mesh
from distributed_ddpg_tpu.parallel.prefetch import ChunkPrefetcher, PrefetchTimeout
from distributed_ddpg_tpu.replay.device import (
    DevicePrioritizedReplay,
    DeviceReplay,
    IngestError,
)
from distributed_ddpg_tpu.replay.staging import HostStagingRing
from distributed_ddpg_tpu.types import packed_width

OBS, ACT = 4, 2
W = packed_width(OBS, ACT)

# Irregular inflow: sub-block trickles, exact blocks, multi-block bursts,
# and enough total volume to wrap the 1024-capacity ring.
INFLOW_SIZES = (30, 400, 64, 7, 999, 128, 1000, 3)


def _inflow(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((n, W)).astype(np.float32) for n in INFLOW_SIZES]


def _snap(rep):
    return (
        np.asarray(jax.device_get(rep.storage)),
        int(jax.device_get(rep.ptr)),
        int(jax.device_get(rep.size)),
    )


def _mk(cls=DeviceReplay, **kw):
    mesh = make_mesh(-1, 1)
    kw.setdefault("block_size", 64)
    return cls(capacity=1024, obs_dim=OBS, act_dim=ACT, mesh=mesh, **kw)


# --------------------------------------------------------------------------
# Host staging ring
# --------------------------------------------------------------------------

def test_ring_fifo_wrap_growth_and_peek():
    ring = HostStagingRing(3, 4)
    rows = np.arange(30, dtype=np.float32).reshape(10, 3)
    ring.push(rows[:2])
    assert len(ring) == 2 and ring.capacity == 4
    np.testing.assert_array_equal(ring.pop(1), rows[:1])
    ring.push(rows[2:5])            # head=1, tail wraps
    assert len(ring) == 4
    np.testing.assert_array_equal(ring.peek(4), rows[1:5])  # FIFO across wrap
    np.testing.assert_array_equal(ring.peek_cols(1, 2, 10), rows[1:5, 1:3])
    np.testing.assert_array_equal(ring.pop(4), rows[1:5])
    ring.push(rows)                  # 10 > capacity 4 -> grows, FIFO intact
    assert ring.capacity >= 10 and len(ring) == 10
    np.testing.assert_array_equal(ring.pop(10), rows)
    with pytest.raises(ValueError):
        ring.pop(1)


def test_ring_pop_is_owned_copy():
    ring = HostStagingRing(2, 8)
    a = np.ones((3, 2), np.float32)
    ring.push(a)
    out = ring.pop(3)
    ring.push(np.full((8, 2), 7.0, np.float32))  # overwrite the region
    np.testing.assert_array_equal(out, a)        # popped rows unaffected


# --------------------------------------------------------------------------
# Coalesced / async parity vs the seed's serial ship sequence
# --------------------------------------------------------------------------

def test_coalesced_parity_with_serial():
    serial = _mk(max_coalesce=1)     # the seed's block-at-a-time sequence
    coal = _mk(max_coalesce=8)
    for b in _inflow():
        serial.add_packed(b)
        coal.add_packed(b)
    assert serial.pending_rows == coal.pending_rows
    serial.flush()
    coal.flush()
    s0, p0, n0 = _snap(serial)
    s1, p1, n1 = _snap(coal)
    assert (p0, n0) == (p1, n1)
    np.testing.assert_array_equal(s0, s1)


def test_async_shipper_parity_with_serial():
    serial = _mk(max_coalesce=1)
    asy = _mk(async_ship=True, max_coalesce=4, staging_blocks=4)
    try:
        for b in _inflow(seed=1):
            serial.add_packed(b)
            asy.add_packed(b)
        asy.drain_pending()
        assert serial.pending_rows == asy.pending_rows
        serial.flush()
        asy.flush()
        s0, p0, n0 = _snap(serial)
        s1, p1, n1 = _snap(asy)
        assert (p0, n0) == (p1, n1)
        np.testing.assert_array_equal(s0, s1)
    finally:
        asy.close()


def test_per_coalesced_async_parity_with_serial():
    """PER: the super-block priority stamp must equal k serial stamps —
    same max_priority (it only changes in the learner), same index range."""
    serial = _mk(DevicePrioritizedReplay, max_coalesce=1)
    asy = _mk(DevicePrioritizedReplay, async_ship=True, max_coalesce=8)
    try:
        for b in _inflow(seed=2):
            serial.add_packed(b)
            asy.add_packed(b)
        asy.drain_pending()
        serial.flush()
        asy.flush()
        s0, p0, n0 = _snap(serial)
        s1, p1, n1 = _snap(asy)
        assert (p0, n0) == (p1, n1)
        np.testing.assert_array_equal(s0, s1)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(serial.priorities)),
            np.asarray(jax.device_get(asy.priorities)),
        )
    finally:
        asy.close()


def test_scheduler_adaptive_pool_parity_with_serial():
    """The full transfer-scheduler ingest path (scheduled work items +
    adaptive coalesce cap + pooled host buffers, docs/TRANSFER.md) must
    leave storage/ptr/size bit-identical to the seed's serial sequence —
    the adaptive cap only changes WHEN rows land, never WHERE."""
    from distributed_ddpg_tpu.transfer import TransferScheduler

    serial = _mk(max_coalesce=1)
    sched = TransferScheduler().start()
    try:
        via = _mk(
            async_ship=True, max_coalesce=8, staging_blocks=4,
            scheduler=sched, adaptive_coalesce=True, host_pool=True,
        )
        assert via._shipper is None, "scheduler path must not spawn a thread"
        for b in _inflow(seed=7):
            serial.add_packed(b)
            via.add_packed(b)
        via.drain_pending()
        assert serial.pending_rows == via.pending_rows
        serial.flush()
        via.flush()
        s0, p0, n0 = _snap(serial)
        s1, p1, n1 = _snap(via)
        assert (p0, n0) == (p1, n1)
        np.testing.assert_array_equal(s0, s1)
        snap = sched.snapshot()
        assert snap["transfer_ingest_items"] >= 1
        assert 1 <= via.transfer_snapshot()["transfer_coalesce_cap"] <= 8
        via.close()
    finally:
        sched.close()


def test_adaptive_cap_jitter_keeps_parity():
    """Adversarial adaptive-cap schedule: force the effective cap through
    an arbitrary trajectory mid-stream and assert bit-identity anyway —
    the structural guarantee the adaptive controller leans on."""
    class _JitterCap:
        def __init__(self):
            self.seq = [1, 4, 2, 8, 1, 2, 4, 8]
            self.i = 0

        def cap(self):
            self.i += 1
            return self.seq[self.i % len(self.seq)]

        def observe_ship(self, blocks, ship_s, queue_rows):
            pass

        def snapshot(self):
            return {}

    serial = _mk(max_coalesce=1)
    jit = _mk(max_coalesce=8)
    jit._adaptive = _JitterCap()
    for b in _inflow(seed=8):
        serial.add_packed(b)
        jit.add_packed(b)
    serial.flush()
    jit.flush()
    s0, p0, n0 = _snap(serial)
    s1, p1, n1 = _snap(jit)
    assert (p0, n0) == (p1, n1)
    np.testing.assert_array_equal(s0, s1)


def test_per_scheduler_parity_with_serial():
    """PER through the scheduler path: priority stamps must equal the
    serial sequence's too (same max_priority, same index ranges)."""
    from distributed_ddpg_tpu.transfer import TransferScheduler

    serial = _mk(DevicePrioritizedReplay, max_coalesce=1)
    sched = TransferScheduler().start()
    try:
        via = _mk(
            DevicePrioritizedReplay, async_ship=True, max_coalesce=8,
            scheduler=sched, adaptive_coalesce=True, host_pool=True,
        )
        for b in _inflow(seed=9):
            serial.add_packed(b)
            via.add_packed(b)
        via.drain_pending()
        serial.flush()
        via.flush()
        s0, p0, n0 = _snap(serial)
        s1, p1, n1 = _snap(via)
        assert (p0, n0) == (p1, n1)
        np.testing.assert_array_equal(s0, s1)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(serial.priorities)),
            np.asarray(jax.device_get(via.priorities)),
        )
        via.close()
    finally:
        sched.close()


def test_scheduler_ingest_failure_bounded_restart():
    """A failing ingest work item recovers through the same bounded
    budget as a dying _IngestShipper thread, then surfaces IngestError."""
    from distributed_ddpg_tpu.transfer import TransferScheduler

    class Boom(DeviceReplay):
        def _ship(self, chunk):
            raise RuntimeError("boom h2d")

    sched = TransferScheduler().start()
    try:
        rep = _mk(Boom, async_ship=True, scheduler=sched)
        rows = np.zeros((64, W), np.float32)
        with pytest.raises(IngestError, match="shipper thread died"):
            for _ in range(300):
                rep.add_packed(rows)
                time.sleep(0.01)
            pytest.fail("scheduler-path ingest death never surfaced")
        assert rep.ingest_snapshot()["ingest_shipper_restarts"] == 3
        rep.close()
    finally:
        sched.close()


def test_reward_sample_includes_staged_rows():
    rep = _mk()
    rows = np.zeros((30, W), np.float32)
    rows[:, OBS + ACT] = 3.5      # reward column
    rows[:, OBS + ACT + 1] = 0.9  # discount column
    rep.add_packed(rows)          # sub-block: stays staged
    assert len(rep) == 0 and rep.pending_rows == 30
    r, d = rep.reward_sample()
    assert r.shape == (30,)
    np.testing.assert_allclose(r, 3.5)
    np.testing.assert_allclose(d, 0.9)


# --------------------------------------------------------------------------
# Backpressure, observability, error surfacing
# --------------------------------------------------------------------------

def test_ingest_stats_and_queue_drain():
    asy = _mk(async_ship=True, max_coalesce=4, staging_blocks=2)
    try:
        rng = np.random.default_rng(3)
        for _ in range(10):
            asy.add_packed(rng.standard_normal((64, W)).astype(np.float32))
        asy.drain_pending()
        snap = asy.ingest_snapshot()
        for key in (
            "ingest_rows_per_sec", "ingest_ship_calls",
            "ingest_coalesce_mean", "ingest_stall_ms", "ingest_ship_ms",
            "ingest_queue_rows",
        ):
            assert key in snap, key
        assert snap["ingest_ship_calls"] >= 1
        assert snap["ingest_coalesce_mean"] >= 1.0
        assert snap["ingest_queue_rows"] == 0
        assert len(asy) == 640
    finally:
        asy.close()


def test_shipper_death_surfaces_named_error():
    class Boom(DeviceReplay):
        def _ship(self, chunk):
            raise RuntimeError("boom h2d")

    rep = _mk(Boom, async_ship=True)
    try:
        rows = np.zeros((64, W), np.float32)
        with pytest.raises(IngestError, match="shipper thread died"):
            for _ in range(200):     # shipper dies on the first full block
                rep.add_packed(rows)
                time.sleep(0.01)
            pytest.fail("shipper death never surfaced")
    finally:
        rep.close()


def test_close_falls_back_to_inline_shipping():
    asy = _mk(async_ship=True)
    asy.close()
    asy.add_packed(np.zeros((64, W), np.float32))  # inline path post-close
    assert len(asy) == 64


# --------------------------------------------------------------------------
# ChunkPrefetcher stop/timeout hardening
# --------------------------------------------------------------------------

class _TinyReplay:
    def __init__(self, delay=0.0):
        self.delay = delay

    def sample(self, n):
        if self.delay:
            time.sleep(self.delay)
        return {"x": np.zeros(n, np.float32), "indices": np.arange(n)}


def test_prefetch_stop_returns_even_with_wedged_put():
    release = threading.Event()

    def wedged_put(chunk):
        release.wait(30.0)
        return chunk

    pf = ChunkPrefetcher(_TinyReplay(), wedged_put, 4, 2, depth=1).start()
    time.sleep(0.3)  # let the worker enter the wedged transfer
    t0 = time.monotonic()
    with pytest.warns(UserWarning, match="did not exit"):
        ok = pf.stop(timeout=0.5)
    assert not ok
    assert time.monotonic() - t0 < 3.0, "stop() must not hang on a wedged put"
    release.set()  # let the leaked daemon thread finish


def test_prefetch_stop_skips_put_after_stop():
    puts = []

    def counting_put(chunk):
        puts.append(1)
        return chunk

    pf = ChunkPrefetcher(_TinyReplay(delay=0.4), counting_put, 4, 1, depth=1)
    pf.start()
    time.sleep(0.1)            # worker is inside sample()
    assert pf.stop(timeout=5.0)
    assert not puts, "stop observed between sample and put must skip the put"


def test_prefetch_next_timeout_raises_named_error():
    release = threading.Event()

    def wedged_put(chunk):
        release.wait(30.0)
        return chunk

    pf = ChunkPrefetcher(_TinyReplay(), wedged_put, 4, 2, depth=1).start()
    try:
        with pytest.raises(PrefetchTimeout, match="worker alive"):
            pf.next(timeout=0.4)
    finally:
        release.set()
        pf.stop()
