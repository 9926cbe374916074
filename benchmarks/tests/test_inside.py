"""The readers of what the program measures from inside (harness/inside.py
and the metric files over it): on made records, on a made trace with known
gaps and spans, and on a trace recorded on the chip with the program's
annotations in it."""

import importlib
import json
import os

import pytest

from conftest import BENCH, ROOT
from harness import inside, xplane

US = 1000.0  # the made trace is written in microseconds; the file's clock is nanoseconds


def read(metric, run):
    return importlib.import_module("metrics." + metric.replace(".", "_")).read(run)


def made_trace():
    """One chip, 100 us traced. Ops run 0-20, 30-50, 60-90: idle 20-30,
    50-60 and 90-100, 30% of the span. The learner thread dispatches over
    18-24 and 48-52, refreshes over 52-58 with a d2h nested in it, ingests
    over 95-99; another thread ships rows the whole time."""
    ops = [("fusion.1", 0.0, 20 * US), ("fusion.2", 30 * US, 20 * US), ("fusion.1", 60 * US, 30 * US)]
    trace = {
        "device": {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": [("jit_chunk(1)", 0.0, 90 * US)]}},
        "host": [("transfer_ingest", 0.0, 100 * US)],
    }
    lines = {
        "learner": [
            ("dispatch", 18 * US, 6 * US), ("dispatch", 48 * US, 4 * US),
            ("refresh", 52 * US, 6 * US), ("params_d2h", 53 * US, 4 * US),
            ("ingest", 95 * US, 4 * US),
        ],
        "shipper": [("transfer_ingest", 0.0, 100 * US), ("ingest_ship", 10 * US, 5 * US)],
    }
    return trace, lines


PHASES = frozenset({"dispatch", "ingest", "refresh", "sync"})


def test_idle_shares_on_a_made_trace_add_up_to_the_idle_share():
    trace, lines = made_trace()
    shares = inside.idle_shares(trace, lines, PHASES)
    assert shares["dispatch"] == pytest.approx(4 + 2)  # 20-24 and 50-52
    assert shares["refresh"] == pytest.approx(6)  # 52-58; the d2h nested in it is the phase's, not its own
    assert shares["ingest"] == pytest.approx(4)  # 95-99
    assert shares["sync"] == 0  # no such span in the trace: nothing idle under it
    assert shares[None] == pytest.approx(6 + 2 + 6)  # 24-30, 58-60, 90-95 and 99-100
    idle = 100.0 * (1.0 - xplane.reduce(trace)["busy_s"] / xplane.reduce(trace)["window_s"])
    assert sum(shares.values()) == pytest.approx(idle) and idle == pytest.approx(30)


def test_idle_shares_average_over_chips_and_find_the_learner_by_its_spans():
    trace, lines = made_trace()
    trace["device"]["/device:TPU:1"] = {"XLA Ops": [("fusion.1", 0.0, 100 * US)]}  # never idle
    lines["noise"] = [("dispatch", 0.0, 100 * US)]  # one lookalike span: the learner's line has more
    shares = inside.idle_shares(trace, lines, PHASES)
    assert shares["dispatch"] == pytest.approx(3) and shares[None] == pytest.approx(7)
    assert sum(shares.values()) == pytest.approx(15)


def test_a_trace_with_no_program_span_leaves_all_idle_unattributed():
    trace, lines = made_trace()
    shares = inside.idle_shares(trace, {"shipper": lines["shipper"]}, PHASES)
    assert shares[None] == pytest.approx(30) and all(shares[p] == 0 for p in PHASES)
    assert inside.idle_shares({"device": {}, "host": []}, {}, PHASES) is None


def test_overlap_is_the_intersection_of_sorted_interval_lists():
    assert inside.overlap([(0, 10), (20, 30)], [[5, 25]]) == 10
    assert inside.overlap([(0, 10)], [[0, 2], [3, 4], [9, 50]]) == 4
    assert inside.overlap([(0, 10)], []) == 0 and inside.overlap([], [[0, 1]]) == 0


def window_records():
    return [
        {"n_dispatch": 100, "t_dispatch_ms": 1.8, "launches_in_flight_mean": 20.0,
         "launches_in_flight_max": 31, "n_dispatch_starved": 1, "n_refresh": 5, "t_refresh_ms": 150.0},
        {"n_dispatch": 300, "t_dispatch_ms": 1.8, "launches_in_flight_mean": 28.0,
         "launches_in_flight_max": 33, "n_dispatch_starved": 3, "n_pod_obs": 1, "t_pod_obs_ms": 2.0},
        {},
    ]


def test_launch_queue_readers_weigh_each_record_by_its_dispatches():
    run = {"window": window_records()}
    assert read("loop.launches_in_flight", run) == pytest.approx(26.0)
    assert read("loop.starved_dispatch_pct", run) == pytest.approx(1.0)
    assert inside.phases_of(run["window"]) == {"dispatch", "refresh", "pod_obs"}  # not `dispatch_starved`
    parent = {"window": [{"n_dispatch": 100, "t_dispatch_ms": 1.8}]}  # a program without the counter
    assert read("loop.launches_in_flight", parent) is None
    assert read("loop.starved_dispatch_pct", parent) is None


SPANS = {"setup_import": 47.1, "setup_backend": 11.6, "setup_build": 4.5, "setup_fill": 3.2, "setup_first_chunk": 6.0}


@pytest.mark.parametrize("stage", ["import", "backend", "build", "fill", "first_chunk"])
def test_setup_readers_take_their_stage_from_the_summary(stage):
    run = {"summary": {"setup_spans": SPANS, "setup_compile_s": 38.7}}
    assert read(f"setup.{stage}_s", run) == SPANS[f"setup_{stage}"]
    assert read("setup.compile_s", run) == 38.7
    assert read(f"setup.{stage}_s", {"summary": {}}) is None  # a program that writes no stages
    assert read("setup.compile_s", {"summary": {}}) is None


def test_insert_readers_find_the_named_programs_and_read_zero_where_none_ran():
    launches = {
        "jit_fused_sample_chunk_fn": {"count": 300, "median_s": 6.4e-3, "total_s": 1.92},
        "jit_ring_insert": {"count": 30, "median_s": 0.17e-3, "total_s": 0.0051},
        "jit_ring_insert_stamp": {"count": 30, "median_s": 0.01e-3, "total_s": 0.0003},
    }
    run = {"summary": {"setup_spans": SPANS}, "trace": {"launches": launches, "busy_s": 2.0}}
    assert read("ingest.insert_device_ms", run) == pytest.approx(0.17)
    assert read("ingest.device_share_pct", run) == pytest.approx(0.27)
    del launches["jit_ring_insert"], launches["jit_ring_insert_stamp"]
    assert read("ingest.insert_device_ms", run) is None  # no insert in the traced span
    assert read("ingest.device_share_pct", run) == 0
    # a program that does not name its inserts (its summary has no set-up stages): nothing to read
    launches["jit__insert_impl"] = {"count": 30, "median_s": 0.17e-3, "total_s": 0.0051}
    old = {"summary": {}, "trace": run["trace"]}
    assert read("ingest.insert_device_ms", old) is None and read("ingest.device_share_pct", old) is None
    assert read("ingest.device_share_pct", {"summary": {"setup_spans": SPANS}, "trace": None}) is None


@pytest.mark.parametrize("metric", ["idle.dispatch_pct", "idle.ingest_pct", "idle.refresh_pct",
                                    "idle.sync_pct", "idle.unattributed_pct"])
def test_idle_readers_have_nothing_to_read_without_a_trace_or_without_spans(metric, tmp_path):
    summary = {"setup_spans": SPANS, "log_path": str(tmp_path / "records.jsonl")}
    assert read(metric, {"summary": summary, "trace": None, "window": window_records()}) is None
    assert read(metric, {"summary": {}, "trace": {"busy_s": 1.0}, "window": []}) is None  # the parent's program
    # a trace was reduced, but its file is not beside the records
    assert read(metric, {"summary": summary, "trace": {"busy_s": 1.0}, "window": window_records()}) is None
    assert read(metric, {"summary": {"setup_spans": SPANS}, "trace": {"busy_s": 1.0}, "window": []}) is None


def test_every_new_metric_is_appended_with_a_layer_the_benchmark_has():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    per_layer = {m["name"]: m for m in b["per_layer"]}
    layers = {m["layer"] for m in b["per_layer"][:8]}  # the accepted benchmark's
    new = [n for n in per_layer if n.startswith(("setup.", "idle.", "ingest.insert", "ingest.device", "loop.launches", "loop.starved"))]
    assert len(new) == 15
    for name in new:
        m = per_layer[name]
        assert m["layer"] in layers and m["better"] == "lower"
        assert m["moves"] == ("setup_s" if name.startswith("setup.") else "grad_steps_per_s")
        assert os.path.isfile(os.path.join(BENCH, "metrics", name.replace(".", "_") + ".py"))


RECORDED = os.path.join(BENCH, "tests", "data", "ddpg_spans_40ms.xplane.pb")


def test_idle_by_phase_on_a_trace_recorded_on_the_chip_with_the_programs_spans_in_it():
    # 40 ms of the DDPG megakernel cell inside train(), TPU v5 lite, one chip (my chip run, PR 25),
    # cut where a record is written and a refresh follows; spans that reach over an edge are clipped to it
    trace, lines = inside.load(RECORDED)
    r = xplane.reduce(trace)
    assert r["chips"] == 1 and r["window_s"] == pytest.approx(0.040, rel=1e-3)
    assert r["launches"]["jit_fused_sample_chunk_fn"]["median_s"] == pytest.approx(6.384e-3, rel=1e-3)
    # the insert program is found by the name the program gave it on purpose
    assert r["launches"]["jit_ring_insert"]["count"] == 2
    assert not any("insert_impl" in name for name in r["launches"])
    # the longest gaps carry the program's spans' names: the device ran dry under the metrics
    # read-back, then under the parameter refresh
    assert [name for name, _ in r["idle_gaps"][:2]] == ["sync", "refresh"]
    learner = inside.learner_line(lines, PHASES)
    names = [name for name, _, _ in learner]
    assert names.count("dispatch") == 12 and names.count("refresh") == 1 and names.count("sync") == 1
    assert {"params_d2h", "metrics_d2h", "param_broadcast"} <= set(names)  # children, nested by time
    assert not any(name == "dispatch" for k, v in lines.items() if v is not learner for name, _, _ in v)
    shares = inside.idle_shares(trace, lines, PHASES)
    idle = 100.0 * (1.0 - r["busy_s"] / r["window_s"])
    assert sum(shares.values()) == pytest.approx(idle) and idle == pytest.approx(18.35, abs=0.01)
    assert shares["sync"] == pytest.approx(10.52, abs=0.01)
    assert shares["refresh"] == pytest.approx(5.38, abs=0.01)
    assert shares["dispatch"] == pytest.approx(0.047, abs=0.002) and shares["ingest"] < 0.001
    assert shares[None] == pytest.approx(2.40, abs=0.01)  # after the sync, while the loop writes its record


def test_recorded_dispatch_spans_carry_the_launch_index_and_the_queue_depth():
    from jax.profiler import ProfileData

    spans = sorted(
        (e.start_ns, dict(e.stats))
        for plane in ProfileData.from_file(RECORDED).planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events if e.name == "dispatch"
    )
    chunks = [stats["chunk"] for _, stats in spans]
    assert chunks == list(range(chunks[0], chunks[0] + 12))  # one span per launch, in order
    depths = [stats["in_flight"] for _, stats in spans]
    # the record's read-back and the refresh each drained the queue; between them it grows by one a dispatch
    assert depths.count(0) == 2 and max(depths) < 12
    refresh = [
        dict(e.stats) for plane in ProfileData.from_file(RECORDED).planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events if e.name == "refresh"
    ]
    assert len(refresh) == 1 and refresh[0]["learner_step"] % 800 == 0


def test_the_readers_find_the_recorded_trace_beside_the_records(tmp_path):
    import shutil

    where = tmp_path / "trace" / "plugins" / "profile" / "2026_09_28"
    where.mkdir(parents=True)
    shutil.copy(RECORDED, where / "host.xplane.pb")
    run = {
        "summary": {"setup_spans": SPANS, "log_path": str(tmp_path / "records.jsonl")},
        "trace": xplane.reduce(xplane.load(RECORDED)),
        "window": window_records(),
    }
    five = {m: read(m, run) for m in ("idle.dispatch_pct", "idle.ingest_pct", "idle.refresh_pct",
                                      "idle.sync_pct", "idle.unattributed_pct")}
    assert all(v is not None and v >= 0 for v in five.values())
    assert sum(five.values()) == pytest.approx(read("device.idle_pct", run))
    assert five["idle.refresh_pct"] == pytest.approx(5.38, abs=0.01)
    assert read("ingest.insert_device_ms", run) == pytest.approx(0.251, abs=0.001)
    assert read("ingest.device_share_pct", run) == pytest.approx(1.54, abs=0.01)
    assert inside.load.cache_info().misses <= 2  # the file is parsed once, not once per metric
