"""MetricsLogger tests (SURVEY.md §5 'Metrics / logging'): JSONL records,
field-type preservation, PhaseTimers tail latencies, and the counters the
learner loop keeps from inside: launches in flight, set-up stages, programs
compiled."""

import json
import os
import time

import numpy as np
import pytest

from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.metrics import (
    CompileCounter,
    LaunchQueue,
    MetricsLogger,
    PhaseTimers,
    SetupStages,
    Timer,
    _jsonable,
)


def test_jsonl_records(tmp_path):
    path = tmp_path / "m.jsonl"
    log = MetricsLogger(str(path), echo=False)
    log.log("train", 10, critic_loss=0.5, note="hi")
    log.log("eval", 20, eval_return=-100.0)
    log.close()
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    # The run-start header record (docs/OBSERVABILITY.md §1) always leads.
    assert [r["kind"] for r in recs] == ["header", "train", "eval"]
    assert recs[0]["t_unix_base"] > 0 and recs[0]["pid"] == os.getpid()
    assert recs[1]["critic_loss"] == 0.5
    assert recs[1]["note"] == "hi"          # non-numeric passes through
    assert recs[2]["step"] == 20


def test_jsonable_preserves_bool_and_int_types(tmp_path):
    """The old blanket float() coerced bools to 1.0/0.0 and ints to
    floats in every JSONL record — downstream parsers then can't tell
    `fused_chunk_active: true` from a measured scalar. Native AND numpy
    scalar types must round-trip; float rounding stays."""
    assert _jsonable(True) is True
    assert _jsonable(False) is False
    assert _jsonable(np.bool_(True)) is True
    assert _jsonable(7) == 7 and isinstance(_jsonable(7), int)
    assert _jsonable(np.int64(7)) == 7 and isinstance(_jsonable(np.int64(7)), int)
    assert _jsonable(1.23456789) == 1.234568
    assert _jsonable(np.float32(0.5)) == 0.5
    assert _jsonable("s") == "s" and _jsonable(None) is None

    path = tmp_path / "m.jsonl"
    log = MetricsLogger(str(path), echo=False)
    log.log("train", 1, active=True, count=3, loss=0.25)
    log.close()
    rec = json.loads(path.read_text().splitlines()[-1])
    assert rec["active"] is True
    assert rec["count"] == 3 and not isinstance(rec["count"], float)
    assert rec["loss"] == 0.25


def test_timer_rates():
    t = Timer()
    t.tick(10)
    assert t.rate() > 0
    t.reset()
    assert t.rate() == 0.0


def test_timer_survives_wall_clock_jumps(monkeypatch):
    """Timer measures on the monotonic clock: a wall-clock step (NTP,
    manual date set) mid-window must not distort the rate."""
    t = Timer()
    t.tick(100)
    # A wall-clock jump would change time.time() arbitrarily; the rate
    # must derive from time.monotonic() only.
    real_time = time.time
    monkeypatch.setattr(time, "time", lambda: real_time() + 3600.0)
    rate = t.rate()
    assert rate > 10  # 100 ticks over ms-scale elapsed, not over an hour


def test_phase_timers_percentiles_and_reset():
    p = PhaseTimers()
    for i in range(40):
        with p.phase("dispatch"):
            # One 25ms outlier against fast calls: sleep granularity on a
            # busy box is ~1ms, so the outlier is placed 10x above any
            # plausible jitter on the fast path.
            time.sleep(0.025 if i == 39 else 0.0002)
    snap = p.snapshot()
    assert snap["n_dispatch"] == 40
    for key in ("t_dispatch_ms", "t_dispatch_p50", "t_dispatch_p95",
                "t_dispatch_max"):
        assert key in snap, key
    # Ordering invariants of a (mean, p50, p95, max) family over a
    # distribution with one large outlier.
    assert snap["t_dispatch_p50"] <= snap["t_dispatch_p95"] <= snap["t_dispatch_max"]
    assert snap["t_dispatch_max"] >= 20.0  # the 25ms outlier, in ms
    assert snap["t_dispatch_p50"] < 15.0   # the typical fast call
    # Interval reset: the next snapshot starts fresh.
    assert p.snapshot() == {}


def test_phase_timers_emit_trace_spans():
    """Every phase bracket doubles as a flight-recorder span (the same
    bracket feeds the scalar record and the Perfetto timeline)."""
    from distributed_ddpg_tpu import trace

    trace.configure(capacity=64)
    try:
        p = PhaseTimers()
        with p.phase("ckpt"):
            pass
        spans = [e for e in trace.get().events() if e["ph"] == "X"]
        assert any(e["name"] == "ckpt" for e in spans)
    finally:
        trace.disable()


# --------------------------------------------------------------------------
# LaunchQueue: launches in flight, counted without a sync
# --------------------------------------------------------------------------

class _Leaf:
    """A chunk's output leaf whose readiness the test controls."""

    def __init__(self):
        self.ready = False

    def is_ready(self):
        return self.ready


def _dispatch(q, leaf, updates=800):
    done = q.poll()
    q.add(leaf, updates)
    return done


def test_launch_queue_depth_max_and_finished_updates():
    q = LaunchQueue()
    a, b, c, d = _Leaf(), _Leaf(), _Leaf(), _Leaf()
    assert _dispatch(q, a) == 0 and len(q) == 1
    assert _dispatch(q, b) == 0 and len(q) == 2
    assert _dispatch(q, c) == 0 and len(q) == 3
    a.ready = b.ready = True  # the device finished the two oldest
    assert _dispatch(q, d) == 1600 and len(q) == 2
    assert q.steps_done == 1600 and q.n_dispatched == 4
    snap = q.snapshot()
    # depths seen by the four dispatches, each counting itself: 1, 2, 3, 2
    assert snap == {
        "launches_in_flight_mean": 2.0, "launches_in_flight_max": 3,
        "n_dispatch_starved": 0,
    }
    assert q.snapshot() == {
        "launches_in_flight_mean": 0.0, "launches_in_flight_max": 0,
        "n_dispatch_starved": 0,
    }  # interval-scoped; the queue itself is not reset
    assert len(q) == 2 and q.steps_done == 1600


def test_launch_queue_finishes_in_order_only():
    q = LaunchQueue()
    a, b = _Leaf(), _Leaf()
    _dispatch(q, a)
    _dispatch(q, b)
    b.ready = True  # launches finish in dispatch order: b cannot be done before a
    assert q.settle() == 0 and len(q) == 2
    a.ready = True
    assert q.settle() == 1600 and len(q) == 0 and q.steps_done == 1600


def test_launch_queue_counts_a_dispatch_that_found_the_device_idle():
    q = LaunchQueue()
    a, b, c = _Leaf(), _Leaf(), _Leaf()
    _dispatch(q, a, updates=8)  # the run's first dispatch is set-up, not starvation
    a.ready = True
    assert _dispatch(q, b, updates=8) == 8  # nothing was queued when the host came back
    assert _dispatch(q, c, updates=8) == 0  # b still running: not starved
    snap = q.snapshot()
    assert snap["n_dispatch_starved"] == 1 and snap["launches_in_flight_max"] == 2
    b.ready = c.ready = True
    assert q.settle() == 16 and q.steps_done == 24  # settle() outside a dispatch starves nothing
    assert q.snapshot()["n_dispatch_starved"] == 0


# --------------------------------------------------------------------------
# SetupStages, CompileCounter: set-up measured from inside
# --------------------------------------------------------------------------

def test_setup_stages_accumulate_by_name_in_the_order_first_staged():
    s = SetupStages()
    s.add("setup_import", 0.5)  # measured before anything could bracket it
    t0 = time.perf_counter()
    s.stage("setup_import")
    time.sleep(0.01)
    s.stage("setup_build")
    time.sleep(0.02)
    s.stage("setup_fill")
    s.end()
    s.end()  # idempotent
    wall = time.perf_counter() - t0
    assert list(s.spans) == ["setup_import", "setup_build", "setup_fill"]
    assert s.spans["setup_import"] >= 0.51 and s.spans["setup_build"] >= 0.02
    assert sum(s.spans.values()) - 0.5 <= wall  # disjoint: together no longer than the stretch they cover


def test_setup_stages_are_disjoint_spans_in_the_ring_each_ending_where_the_next_begins():
    rec = trace.configure(capacity=64)
    try:
        s = SetupStages()
        s.stage("setup_build")
        time.sleep(0.005)
        s.stage("setup_fill")
        s.stage("setup_first_chunk")
        s.end()
        spans = sorted((e for e in rec.events() if e["ph"] == "X"), key=lambda e: e["ts"])
    finally:
        trace.disable()
    assert [e["name"] for e in spans] == ["setup_build", "setup_fill", "setup_first_chunk"]
    for a, b in zip(spans, spans[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1.0  # microseconds


def test_compile_counter_counts_builds_that_were_not_cache_hits():
    import jax.monitoring as m

    c = CompileCounter().install()
    try:
        m.record_event_duration_secs(CompileCounter.BUILD, 2.5)  # compiled
        m.record_event(CompileCounter.HIT)  # JAX: the hit, then its build's duration
        m.record_event_duration_secs("/jax/compilation_cache/cache_retrieval_time_sec", 0.01)
        m.record_event_duration_secs(CompileCounter.BUILD, 0.02)  # loaded from the cache
        m.record_event_duration_secs(CompileCounter.BUILD, 0.5)  # compiled
    finally:
        c.uninstall()
    c.uninstall()  # idempotent
    m.record_event_duration_secs(CompileCounter.BUILD, 9.0)  # after set-up: not counted
    assert c.programs == 2 and c.seconds == pytest.approx(3.0)
