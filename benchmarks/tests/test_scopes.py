"""The readers of device time by the program's own scopes (harness/scopes.py
and the seven metric files over it): on made traces with a loop that holds
its body's events, and on a trace recorded on the chip under a hand-written
table."""

import importlib
import json
import os

import pytest

from conftest import BENCH, ROOT
from harness import scopes, xplane

US = 1000.0  # the made traces are written in microseconds; the file's clock is nanoseconds
SEVEN = ["chunk.sample_ms", "chunk.prep_ms", "chunk.update_ms", "chunk.collective_ms",
         "chunk.optim_pct", "chunk.update_gap_pct", "chunk.unscoped_pct"]
TABLE = {
    "module": "jit_chunk",
    "ops": {
        "fusion.1": "draw", "fusion.2": "gather", "slice.3": "cut", "while.4": "update",
        "fusion.5": "update/critic", "fusion.6": "update/optim", "conditional.7": "update",
        "fusion.8": "update/actor", "all-reduce.9": "collective", "reduce.10": "metrics",
    },
    "served": {"all-reduce.9": "update/critic"},
    "loops": ["while.4"],
}


def read(metric, run):
    return importlib.import_module("metrics." + metric.replace(".", "_")).read(run)


def launch_ops(t):
    """One launch of 100 us from `t`: draw 0-5, gather 5-20, cut 20-24, the
    loop 24-90 with two trips (critic 25-35, all-reduce 35-38, optim 38-48;
    critic 50-60, a conditional 60-75 that holds the actor's 62-72, optim
    75-85), an unnamed copy 90-94, metrics 94-96, nothing 96-100."""
    ops = [
        ("%fusion.1 = s32[8,4] fusion(...)", 0, 5), ("fusion.2", 5, 15), ("slice.3", 20, 4),
        ("while.4", 24, 66),
        ("fusion.5", 25, 10), ("all-reduce.9", 35, 3), ("fusion.6", 38, 10),
        ("fusion.5", 50, 10), ("conditional.7", 60, 15), ("fusion.8", 62, 10), ("fusion.6", 75, 10),
        ("copy.11", 90, 4), ("reduce.10", 94, 2),
    ]
    return [(name, t + s * US, d * US) for name, s, d in ops]


def made_trace(chips=1):
    """A launch cut by the trace's start (its last 30 us), three whole ones,
    one cut by its end; an insert program between the second and the third."""
    device = {}
    for chip in range(chips):
        ops, modules = [("copy.11", 0.0, 30 * US)], [("jit_chunk(7)", 0.0, 30 * US)]
        for i, t in enumerate((30, 130, 240)):
            modules.append(("jit_chunk(7)", t * US, 100 * US))
            ops += launch_ops(t * US)
        modules.append(("jit_ring_insert(9)", 230 * US, 8 * US))
        ops.append(("fusion.2", 231 * US, 6 * US))  # another program's op of the same name
        modules.append(("jit_chunk(7)", 340 * US, 40 * US))
        ops += [e for e in launch_ops(340 * US) if e[1] + e[2] <= 380 * US]
        device[f"/device:TPU:{chip}"] = {"XLA Modules": modules, "XLA Ops": ops}
    return {"device": device, "host": []}


def test_self_time_is_duration_less_the_events_nested_in_it():
    own = dict(scopes.self_times([("while", 0, 100), ("a", 10, 20), ("cond", 40, 30), ("b", 45, 10), ("c", 120, 5)]))
    assert own == {"while": 50, "a": 20, "cond": 20, "b": 10, "c": 5}
    # today's reduction counts the loop and its children both; this one adds up to the top-level events
    assert sum(own.values()) == 100 + 5


def test_by_scope_sums_add_up_to_the_launches_op_time_and_the_gap_is_the_loops():
    found = scopes.per_launch(made_trace(), TABLE, "jit_chunk")
    assert found["launches"] == 3  # the two launches the trace cut are left out
    got = {k: v / US for k, v in found["scopes"].items()}
    assert got == pytest.approx({
        "draw": 5, "gather": 15, "cut": 4, "update": (66 - 58) + (15 - 10), "update/critic": 20,
        "update/optim": 20, "update/actor": 10, "collective": 3, "metrics": 2, scopes.UNSCOPED: 4,
    })
    assert found["loop_self"] / US == pytest.approx(66 - 58)  # the conditional's own 5 us is not the loop's
    # the identity: every op's self time lands in one scope, so the scopes add up to the op time
    # inside the launch (the top-level events), here 96 of the launch's 100 us
    assert scopes.ns(found) / US == pytest.approx(96)
    assert scopes.ns(found, "update") / US == pytest.approx(13 + 20 + 20 + 10)
    assert scopes.ns(found, "draw", "gather") / US == pytest.approx(20)


def test_two_chips_average_and_a_chip_without_a_whole_launch_is_left_out():
    trace = made_trace(chips=2)
    ops = trace["device"]["/device:TPU:1"]["XLA Ops"]
    # the second chip's gathers take a third of the time
    trace["device"]["/device:TPU:1"]["XLA Ops"] = [
        (n, s, d / 3 if n == "fusion.2" and d == 15 * US else d) for n, s, d in ops
    ]
    found = scopes.per_launch(trace, TABLE, "jit_chunk")
    assert found["scopes"]["gather"] / US == pytest.approx((15 + 5) / 2)
    assert found["scopes"]["draw"] / US == pytest.approx(5)
    trace["device"]["/device:TPU:2"] = {"XLA Modules": [("jit_chunk(7)", 0.0, 50 * US)], "XLA Ops": [("fusion.2", 0.0, 50 * US)]}
    assert scopes.per_launch(trace, TABLE, "jit_chunk")["scopes"]["gather"] / US == pytest.approx((15 + 5) / 2)
    assert scopes.per_launch(trace, TABLE, "jit_other") is None
    assert scopes.per_launch({"device": {}, "host": []}, TABLE, "jit_chunk") is None


def run_with(tmp_path, table=TABLE, recorded=None):
    """A run whose records directory holds the table and a trace file."""
    import shutil

    where = tmp_path / "trace" / "plugins" / "profile" / "2026_09_29"
    where.mkdir(parents=True)
    if recorded:
        shutil.copy(recorded, where / "host.xplane.pb")
    if table is not None:
        (tmp_path / scopes.TABLE_FILE).write_text(json.dumps(table))
    return {
        "summary": {"log_path": str(tmp_path / "records.jsonl")},
        "trace": {"busy_s": 1.0}, "config": {"chunk_module": "jit_fused_sample_chunk_fn"},
    }


RECORDED = os.path.join(BENCH, "tests", "data", "ddpg_kernel_40ms.xplane.pb")
# What the kernel-leg chunk program of that trace (PR 24's, feature-major ring) would have said of
# its longest ops, written by hand from PERF.md's reading of them.
BY_HAND = {
    "module": "jit_fused_sample_chunk_fn",
    "ops": {"fused_sample_chunk_fn.1": "update", "fusion": "gather", "fusion.37": "draw",
            "fusion.38": "cut", "slice.27": "cut", "copy.96": "cut"},
    "served": {}, "loops": [],
}


def test_the_recorded_chip_trace_reads_its_kernel_as_update_and_its_fusion_as_sample(tmp_path):
    # 40 ms of the DDPG megakernel cell, TPU v5 lite, one chip (my chip run, PR 24): seven launches,
    # the first and the last cut by the trace
    run, parsed = run_with(tmp_path, BY_HAND, RECORDED), scopes._read.cache_info().misses
    found = scopes.of_run(run)
    assert found["launches"] == 5 and found["loop_self"] == 0
    assert read("chunk.update_ms", run) == pytest.approx(3.572, abs=0.001)  # the Pallas call alone
    assert read("chunk.sample_ms", run) == pytest.approx(2.564 + 0.124, abs=0.001)  # `fusion` and the draw
    assert read("chunk.prep_ms", run) == pytest.approx(0.0524, abs=0.0005)
    assert read("chunk.unscoped_pct", run) == pytest.approx(0.995, abs=0.005)  # the copies of the state
    assert read("chunk.optim_pct", run) == 0 and read("chunk.update_gap_pct", run) == 0
    assert read("chunk.collective_ms", run) == 0
    # every op of a launch lands in one scope: together they are the launch less its time under no op
    launch_ms = xplane.reduce(xplane.load(RECORDED))["launches"]["jit_fused_sample_chunk_fn"]["median_s"] * 1e3
    assert scopes.ms(run) == pytest.approx(launch_ms, rel=2e-3) and scopes.ms(run) < launch_ms
    assert scopes._read.cache_info().misses == parsed + 1  # one parse for all the readers


@pytest.mark.parametrize("metric", SEVEN)
def test_every_reader_gives_none_without_the_table_the_trace_or_a_whole_launch(metric, tmp_path):
    (tmp_path / "a").mkdir(), (tmp_path / "b").mkdir(), (tmp_path / "c").mkdir()
    assert read(metric, run_with(tmp_path / "a", table=None, recorded=RECORDED)) is None  # the parent's program
    untraced = run_with(tmp_path / "b", recorded=RECORDED)
    untraced["trace"] = None
    assert read(metric, untraced) is None
    assert read(metric, run_with(tmp_path / "c")) is None  # a table, and no trace file beside the records
    assert read(metric, {"summary": {}, "trace": {"busy_s": 1.0}, "config": {}}) is None
    other = run_with(tmp_path, BY_HAND, RECORDED)
    other["config"] = {"chunk_module": "jit_sample_chunk_fn"}  # no such launch in the trace
    assert read(metric, other) is None


def test_the_seven_entries_are_appended_with_a_layer_the_benchmark_has():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in b["per_layer"]]
    assert names[-7:] == SEVEN
    layers = {m["layer"] for m in b["per_layer"][:-7]}
    cells = {w["name"] for w in b["workloads"]}
    scan = {w["name"] for w in b["workloads"] if w["config"] in ("sac-humanoid", "redq-humanoid")}
    for m in b["per_layer"][-7:]:
        assert m["layer"] == "learner and kernel" and m["layer"] in layers
        assert (m["source"], m["moves"], m["better"]) == ("device_trace", "grad_steps_per_s", "lower")
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"].replace(".", "_") + ".py"))
    by_name = {m["name"]: m for m in b["per_layer"]}
    assert by_name["chunk.collective_ms"]["workloads"] == [w["name"] for w in b["workloads"] if w["chips"] == 4]
    assert set(by_name["chunk.optim_pct"]["workloads"]) == set(by_name["chunk.update_gap_pct"]["workloads"]) == scan
    assert all("workloads" not in by_name[n] for n in ("chunk.sample_ms", "chunk.prep_ms", "chunk.update_ms", "chunk.unscoped_pct"))
