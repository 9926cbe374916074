"""The idle time by where it lies and by the learner thread's innermost span
(harness/timeline.py and the seven metric files over it): on made traces with
known gaps, on made records, on the trace of the parent's program recorded at
PR 25 (no `launch_wait` in it) and on one of the program that drains before it
reads back."""

import importlib
import json
import os
import shutil

import pytest

from conftest import BENCH, ROOT
from harness import inside, timeline, xplane

US = 1000.0  # the made trace is written in microseconds; the file's clock is nanoseconds
NEW = ("loop.refresh_host_ms", "loop.drained_pct", "idle.edge_pct", "idle.drain_pct", "idle.d2h_pct",
       "idle.publish_pct", "idle.between_pct")
NAMES = frozenset(inside.PHASES | timeline.READ_BACK | {"refresh_drain", "sync_drain"})


def read(metric, run):
    return importlib.import_module("metrics." + metric.replace(".", "_")).read(run)


def made_trace():
    """One chip, 100 us traced (a host event of another thread spans it). Ops
    run 10-20, 30-50, 60-90: the tracer's edges 0-10 and 90-100, interior
    gaps 20-30 and 50-60. The learner thread dispatches over 18-24, refreshes
    over 46-58 (waits for two launches until 50 and, the device already dry,
    until 52; copies 52-55; publishes 55-57) and dispatches again 58-62."""
    ops = [("fusion.1", 10 * US, 10 * US), ("fusion.2", 30 * US, 20 * US), ("fusion.1", 60 * US, 30 * US)]
    trace = {
        "device": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": [("jit_chunk(1)", 10 * US, 80 * US)]}},
        "host": [("transfer_ingest", 0.0, 100 * US)],
    }
    lines = {
        "learner": [
            ("dispatch", 18 * US, 6 * US),
            ("refresh", 46 * US, 12 * US), ("refresh_drain", 46 * US, 6 * US),
            ("launch_wait", 46 * US, 4 * US), ("launch_wait", 50 * US, 2 * US),
            ("transfer_d2h", 52 * US, 3.5 * US), ("params_d2h", 52 * US, 3 * US), ("param_broadcast", 55 * US, 2 * US),
            ("dispatch", 58 * US, 4 * US),
        ],
        "shipper": [("transfer_ingest", 0.0, 100 * US), ("ingest_ship", 10 * US, 5 * US)],
    }
    return trace, lines


def idle_pct(trace):
    r = xplane.reduce(trace)
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])


def whole(found):
    return found["edge"] + found["between"] + sum(found["under"].values())


def test_innermost_gives_every_instant_to_the_span_that_began_last():
    spans = [("a", 0, 10), ("b", 2, 3), ("c", 3, 1), ("d", 6, 9), ("e", 20, 1)]  # d outlasts its parent a: cut to it
    assert timeline.innermost(spans) == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"), (5, 6, "a"), (6, 10, "d"), (20, 21, "e")]
    assert timeline.innermost([]) == []


def test_edges_interior_and_innermost_on_a_made_trace_sum_to_the_idle_share():
    trace, lines = made_trace()
    found = timeline.table(trace, lines, NAMES)
    assert found["edge"] == pytest.approx(20)  # 0-10 and 90-100
    under = found["under"]
    assert under["dispatch"] == pytest.approx(4 + 2)  # 20-24 and 58-60
    assert under["launch_wait"] == pytest.approx(2)  # 50-52: dry though a launch was waited for
    assert under["params_d2h"] == pytest.approx(3) and under["param_broadcast"] == pytest.approx(2)
    assert under["refresh"] == pytest.approx(1)  # 57-58: inside the phase, under none of its children
    assert "refresh_drain" not in under  # every instant of it lies under a launch_wait
    assert "transfer_d2h" not in under  # no name of the program's: the gap under it is its parent's or its child's
    assert found["between"] == pytest.approx(6)  # 24-30
    assert whole(found) == pytest.approx(idle_pct(trace)) and idle_pct(trace) == pytest.approx(40)
    assert timeline.edges(trace) == [(10 * US, 10 * US)]


def test_shares_average_over_chips_and_a_chip_with_no_op_is_all_edge():
    trace, lines = made_trace()
    trace["device"]["/device:TPU:1"] = {"XLA Ops": [("fusion.1", 5 * US, 95 * US)]}  # reached 5 us in, never idle after
    found = timeline.table(trace, lines, NAMES)
    assert found["edge"] == pytest.approx((20 + 5) / 2) and found["under"]["params_d2h"] == pytest.approx(1.5)
    assert whole(found) == pytest.approx(idle_pct(trace))
    assert timeline.edges(trace) == [(10 * US, 10 * US), (5 * US, 0)]
    trace["device"]["/device:TPU:2"] = {"XLA Ops": []}
    found = timeline.table(trace, lines, NAMES)
    assert found["edge"] == pytest.approx((20 + 5 + 100) / 3) and whole(found) == pytest.approx(idle_pct(trace))
    assert timeline.table({"device": {}, "host": []}, {}, NAMES) is None and timeline.edges({"device": {}, "host": []}) is None


def test_a_refresh_that_began_before_the_session_still_gives_its_gap_to_its_children():
    trace, lines = made_trace()
    lines["learner"] = [e for e in lines["learner"] if e[0] not in ("refresh", "refresh_drain")]
    del lines["learner"][1]  # and the launch_wait that was open as the session began
    found = timeline.table(trace, lines, NAMES)
    under = found["under"]
    assert under["params_d2h"] == pytest.approx(3) and under["param_broadcast"] == pytest.approx(2)
    assert under["launch_wait"] == pytest.approx(2) and "refresh" not in under
    assert found["between"] == pytest.approx(6 + 1)  # 57-58 has lost its name
    assert whole(found) == pytest.approx(idle_pct(trace))
    # the old reader gives all of it to no phase
    assert inside.idle_shares(trace, lines, inside.PHASES)["refresh"] == 0


def drained_records():
    return [
        {"n_dispatch": 100, "t_dispatch_ms": 1.8, "n_refresh": 5, "t_refresh_ms": 150.0, "n_refresh_drain": 5,
         "t_refresh_drain_ms": 140.0, "n_sync": 1, "t_sync_ms": 80.0, "n_sync_drain": 1, "t_sync_drain_ms": 79.0},
        {"n_dispatch": 300, "t_dispatch_ms": 1.8, "n_refresh": 15, "t_refresh_ms": 110.0, "n_refresh_drain": 15,
         "t_refresh_drain_ms": 102.0, "n_sync": 1, "t_sync_ms": 2.0, "n_sync_drain": 1, "t_sync_drain_ms": 0.0},
        {},
    ]


def test_the_records_readers_take_the_drain_out_of_its_phase():
    from harness import records

    run = {"window": drained_records(), "window_s": 4.0, "records": records}
    assert read("loop.refresh_ms", run) == pytest.approx(120.0)  # the old reader reads what it read
    assert read("loop.refresh_host_ms", run) == pytest.approx((5 * 10 + 15 * 8) / 20)
    assert read("loop.drained_pct", run) == pytest.approx(100 * (170 + 1 + 2) / 4000)
    # no host worker: `refresh` is a pointer swap with no drain under it, `sync` still drains
    swap = [{"n_refresh": 50, "t_refresh_ms": 0.026, "n_sync": 1, "t_sync_ms": 60.0, "n_sync_drain": 1, "t_sync_drain_ms": 59.5}]
    run = {"window": swap, "window_s": 1.0, "records": records}
    assert read("loop.refresh_host_ms", run) == pytest.approx(read("loop.refresh_ms", run)) == pytest.approx(0.026)
    assert read("loop.drained_pct", run) == pytest.approx(100 * (1.3 + 0.5) / 1000)
    # the parent's program: phases without drains
    parent = {"window": [{"n_refresh": 5, "t_refresh_ms": 150.0, "n_sync": 1, "t_sync_ms": 80.0}], "window_s": 1.0, "records": records}
    assert read("loop.refresh_host_ms", parent) is None and read("loop.drained_pct", parent) is None
    assert read("loop.refresh_ms", parent) == 150.0


@pytest.mark.parametrize("metric", NEW[2:])
def test_trace_readers_have_nothing_to_read_without_a_trace_or_its_file(metric, tmp_path):
    summary = {"log_path": str(tmp_path / "records.jsonl")}
    assert read(metric, {"summary": summary, "trace": None, "window": drained_records()}) is None
    assert read(metric, {"summary": {}, "trace": {"busy_s": 1.0}, "window": drained_records()}) is None
    # a trace was reduced, but its file is not beside the records
    assert read(metric, {"summary": summary, "trace": {"busy_s": 1.0}, "window": drained_records()}) is None


def test_the_seven_entries_are_appended_behind_the_48_with_a_layer_and_a_source_the_benchmark_has():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    had, new = b["per_layer"][:48], b["per_layer"][48:55]
    assert [m["name"] for m in new] == list(NEW) and not set(NEW) & {m["name"] for m in had}
    layers, sources = {m["layer"] for m in had}, {m["source"] for m in had}
    for m in new:
        assert m["layer"] == ("loop" if m["name"].startswith("loop.") else "device") and m["layer"] in layers
        assert m["source"] == ("program_span" if m["name"].startswith("loop.") else "device_trace") and m["source"] in sources
        assert m["better"] == "lower" and m["moves"] == "grad_steps_per_s" and "workloads" not in m
        assert m["unit"] == ("ms" if m["name"].endswith("_ms") else "%")
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"].replace(".", "_") + ".py"))


def beside_records(recorded, tmp_path, window):
    where = tmp_path / "trace" / "plugins" / "profile" / "2026_10_03"
    where.mkdir(parents=True)
    shutil.copy(recorded, where / "host.xplane.pb")
    from harness import records

    return {
        "summary": {"setup_spans": {}, "log_path": str(tmp_path / "records.jsonl")},
        "trace": xplane.reduce(xplane.load(recorded)), "window": window, "window_s": 30.0, "records": records,
    }


PARENTS = os.path.join(BENCH, "tests", "data", "ddpg_spans_40ms.xplane.pb")


def test_the_parents_recorded_trace_reads_its_edges_and_nothing_of_a_drain(tmp_path):
    # 40 ms of the DDPG megakernel cell, the program of PR 25: `params_d2h` and `metrics_d2h` hold the wait
    # for the launches in flight, no `launch_wait`, no drain in the records
    run = beside_records(PARENTS, tmp_path, [{"n_dispatch": 100, "t_dispatch_ms": 1.8, "n_refresh": 5, "t_refresh_ms": 150.0}])
    seven = {m: read(m, run) for m in NEW}
    assert [m for m, v in seven.items() if v is None] == [
        "loop.refresh_host_ms", "loop.drained_pct", "idle.drain_pct", "idle.d2h_pct", "idle.publish_pct"]
    found = timeline.of_run(run)
    assert whole(found) == pytest.approx(read("device.idle_pct", run)) == pytest.approx(18.35, abs=0.01)
    assert seven["idle.edge_pct"] == found["edge"] and seven["idle.between_pct"] == found["between"]
    # the table names what the old readers gave to `sync` and `refresh` whole
    old = inside.idle_shares(*inside.load(PARENTS), inside.PHASES)
    under = found["under"]
    assert under["metrics_d2h"] + under.get("sync", 0.0) == pytest.approx(old["sync"], abs=0.05)
    assert under["params_d2h"] + under["param_broadcast"] + under.get("refresh", 0.0) == pytest.approx(old["refresh"], abs=0.05)


DRAINS = os.path.join(BENCH, "tests", "data", "ddpg_drain_40ms.xplane.pb")


def test_the_seven_on_a_trace_recorded_on_the_chip_of_the_program_that_drains(tmp_path):
    # 40 ms of the DDPG megakernel cell inside train(), TPU v5 lite, one chip (my chip run, PR 49), cut 1.23 s
    # into a 2 s trace where a refresh ends: ten launches waited for one by one, the copy, the broadcast, two
    # dispatches; the `refresh` span (117 ms) began before the cut and is clipped to its edge, as PR 25 cut its
    run = beside_records(DRAINS, tmp_path, drained_records())
    assert os.path.getsize(DRAINS) < 300_000
    r = run["trace"]
    assert r["chips"] == 1 and r["window_s"] == pytest.approx(0.040, rel=1e-3)
    assert r["launches"]["jit_fused_sample_chunk_fn"]["median_s"] == pytest.approx(3.769e-3, rel=1e-3)
    seven = {m: read(m, run) for m in NEW}
    assert all(v is not None for v in seven.values())
    assert seven["idle.edge_pct"] == pytest.approx(0, abs=1e-4)  # the cut begins and ends inside a launch
    assert seven["idle.drain_pct"] == pytest.approx(4.93, abs=0.01)  # 1.97 ms: the host learns late that the last launch ended
    assert seven["idle.d2h_pct"] == pytest.approx(2.07, abs=0.01) and seven["idle.publish_pct"] == pytest.approx(1.04, abs=0.01)
    assert seven["idle.between_pct"] == pytest.approx(0.37, abs=0.01)  # broadcast's end to the next dispatch
    found = timeline.of_run(run)
    assert whole(found) == pytest.approx(read("device.idle_pct", run)) == pytest.approx(9.28, abs=0.01)
    # the old readers still read what they read: all of it is the `refresh` phase's
    assert read("idle.refresh_pct", run) == pytest.approx(8.91, abs=0.01)
    assert read("idle.unattributed_pct", run) == pytest.approx(seven["idle.between_pct"])
    # the largest gap carries the phase's name where the parent's scan-leg lines print `unattributed`
    assert r["idle_gaps"][0][0] == "refresh" and r["idle_gaps"][0][1] == pytest.approx(3.637e-3, rel=1e-3)


def test_recorded_launch_waits_carry_their_dispatch_index_and_end_behind_their_launch():
    from jax.profiler import ProfileData

    data = ProfileData.from_file(DRAINS)
    host = [e for p in data.planes if p.name.startswith("/host:") for line in p.lines for e in line.events]
    waits = sorted((e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)["chunk"]) for e in host if e.name == "launch_wait")
    made = sorted((e.start_ns, dict(e.stats)) for e in host if e.name == "dispatch")
    chunks = [c for _, _, c in waits]
    assert chunks == list(range(chunks[0], chunks[0] + 10))  # one span a queued launch, oldest first
    assert [s["chunk"] for _, s in made] == [chunks[-1] + 1, chunks[-1] + 2]  # the index `dispatch` carries
    assert made[0][1]["in_flight"] == 0  # the refresh left nothing in flight
    (device,) = [p for p in data.planes if p.name.startswith("/device:")]
    (modules,) = [line for line in device.lines if line.name == "XLA Modules"]
    ends = sorted(e.start_ns + e.duration_ns for e in modules.events)[:9]
    # a wait ends behind its launch's end on the device plane by the host's lateness in learning of it, 1.9 to
    # 2.2 ms in this recording (the first wait's launch ended before the cut); no wait is longer than one launch and that
    late = [w_end - m_end for (_, w_end, _), m_end in zip(waits[1:], ends)]
    assert len(late) == 9 and all(1.8e6 < d < 2.3e6 for d in late)
    assert all(w_end - w_start < 3.769e6 + 2.3e6 for w_start, w_end, _ in waits)
