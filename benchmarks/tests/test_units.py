"""The harness's arithmetic and rules, without a run."""

import json
import os
import re
import threading
import time

import pytest

from conftest import BENCH, ROOT
from harness import flops, peaks, records, window, xplane


SHAPES = {"obs_dim": 17, "act_dim": 6}
HP = {"hidden": [256, 256], "batch_size": 64}


def DDPG_WORK():
    from reference import ddpg

    return ddpg.work(SHAPES, HP)


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_no_cell_config_or_metric_name_in_the_harness():
    b = bench_json()
    names = {e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for e in b[k]}
    names |= {w["traffic"] for w in b["workloads"]}
    files = [os.path.join(BENCH, "run.py")] + [
        os.path.join(BENCH, "harness", f) for f in os.listdir(os.path.join(BENCH, "harness")) if f.endswith(".py")
    ]
    for path in files:
        text = open(path).read()
        for name in names:
            if name == "free":  # an English word; the mix's name as a whole word in quotes is what counts
                assert '"free"' not in text and "'free'" not in text, (path, name)
            else:
                assert not re.search(r"(?<![\w.-])" + re.escape(name) + r"(?![\w-])", text), (path, name)


def test_every_named_file_exists():
    b = bench_json()
    for c in b["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert os.path.isfile(os.path.join(BENCH, "reference", cfg["reference"]["module"] + ".py"))
        assert set(c["reduced"]) == set(cfg["reduced"])
    for w in b["workloads"]:
        assert os.path.isfile(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    for m in b["end_to_end"] + b["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics", m["name"].replace(".", "_") + ".py")), m["name"]


@pytest.mark.parametrize("updates_per_s", [190e3, 56e3])
def test_roofline_share_at_the_bare_chunk_rates_perf_md_records(updates_per_s):
    # 190k (kernel) and 56k (scan) updates/s: bare-chunk readings, PERF.md section 5 (my chip run, PR 21)
    pct, bound = flops.roofline_pct(DDPG_WORK(), 800, 800 / updates_per_s, peaks.lookup("TPU v5 lite"))
    assert 0 < pct < 20 and bound == "flops"


def test_flops_count_is_bench_pys_for_ddpg_and_doubles_the_critics_for_sac():
    from reference import sac

    d = DDPG_WORK()
    f_a = 2 * 64 * (17 * 256 + 256 * 256 + 256 * 6)
    f_c = 2 * 64 * (17 * 256 + (256 + 6) * 256 + 256 * 1)
    assert d["flops"] == 4 * f_a + 7 * f_c
    s = sac.work(SHAPES, HP)
    f_a2 = 2 * 64 * (17 * 256 + 256 * 256 + 256 * 12)
    assert s["flops"] == 4 * f_a2 + 14 * f_c
    assert s["state_bytes"] > d["state_bytes"] and s["row_bytes"] == d["row_bytes"]


def test_an_unknown_device_kind_is_an_error():
    assert peaks.lookup("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        peaks.lookup("TPU v9 imaginary")


def test_phase_means_weigh_each_record_by_its_calls():
    recs = [{"t_dispatch_ms": 1.0, "n_dispatch": 100}, {"t_dispatch_ms": 3.0, "n_dispatch": 300}, {}]
    assert records.phase_mean_ms(recs, "dispatch") == pytest.approx(2.5)
    assert records.phase_calls(recs, "dispatch") == 400
    assert records.phase_mean_ms(recs, "refresh") is None
    assert records.rate({"learner_steps": 1000}, {"learner_steps": 31000}, "learner_steps", 2.0) == 15000


class QuietCompiles:
    def __init__(self):
        self.last = time.monotonic() - 100.0


def test_window_opens_after_warmup_and_closes_on_the_first_record_past_its_length(tmp_path):
    path = tmp_path / "records.jsonl"
    ended = threading.Event()
    compiles = QuietCompiles()
    win = window.Window(
        str(path), 0.3, {"warm_chunks": 3, "quiet_s": 0.2, "max_warm_s": 5.0}, compiles, end=ended.set
    )
    win.start()
    with open(path, "a", buffering=1) as f:
        f.write(json.dumps({"kind": "header", "learner_chunk": 8}) + "\n")
        f.write(json.dumps({"kind": "train", "step": 10, "learner_steps": 16, "wall_time": 0.1}) + "\n")
        time.sleep(0.05)  # two chunks done: still warming up
        compiles.last = time.monotonic()  # a program was just built: not quiet yet
        f.write(json.dumps({"kind": "train", "step": 20, "learner_steps": 80, "wall_time": 0.2}) + "\n")
        time.sleep(0.3)
        f.write(json.dumps({"kind": "train", "step": 30, "learner_steps": 160, "wall_time": 0.5}) + "\n")
        f.write('{"kind": "train", "step": 3')  # a torn line is not a record
        time.sleep(0.15)
        f.write('5, "learner_steps": 240, "wall_time": 0.65}\n')
        time.sleep(0.25)
        f.write(json.dumps({"kind": "train", "step": 40, "learner_steps": 320, "wall_time": 0.9}) + "\n")
    assert ended.wait(2.0)
    win.join(2.0)
    assert win.error is None
    assert win.train[win.open_i]["learner_steps"] == 160
    assert win.train[win.close_i]["learner_steps"] == 320
    assert [r["learner_steps"] for r in win.window_records] == [240, 320]
    assert win.t_close - win.t_open >= 0.3


def test_window_gives_up_at_its_deadline(tmp_path):
    ended = threading.Event()
    win = window.Window(str(tmp_path / "never.jsonl"), 1.0, {}, QuietCompiles(), deadline_s=0.1, end=ended.set)
    win.start()
    assert ended.wait(2.0)
    assert win.close_i is None and "deadline" in win.error


def test_xplane_reduction_on_a_made_trace():
    # two chips; chip 0 busy 6 of 10 us, chip 1 busy 4 of 10 us; host span covers the long gap
    trace = {
        "device": {
            "/device:TPU:0": {
                "XLA Modules": [("jit_chunk(1)", 0.0, 4000.0), ("jit_chunk(1)", 8000.0, 2000.0)],
                "XLA Ops": [("fusion.1", 0.0, 3000.0), ("fusion.2", 2500.0, 1500.0), ("fusion.1", 8000.0, 2000.0)],
            },
            "/device:TPU:1": {"XLA Ops": [("fusion.1", 1000.0, 4000.0)]},
        },
        "host": [("params_d2h", 4100.0, 2300.0)],
    }
    r = xplane.reduce(trace)
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(5e-6)
    assert r["chips"] == 2
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(4.5e-6)]
    assert r["launches"]["jit_chunk"]["count"] == 2
    assert r["launches"]["jit_chunk"]["median_s"] == pytest.approx(3e-6)
    assert r["idle_gaps"][0] == ["unattributed", pytest.approx(5e-6)]  # chip 1, 5..10 us: nothing covers half of it
    assert ["params_d2h", pytest.approx(4e-6)] in r["idle_gaps"]
    assert xplane.reduce({"device": {}, "host": []}) is None


def test_xplane_reduction_on_a_trace_recorded_on_the_chip():
    # 40 ms of the DDPG megakernel cell inside train(), TPU v5 lite, one chip (my chip run, PR 24)
    path = os.path.join(BENCH, "tests", "data", "ddpg_kernel_40ms.xplane.pb")
    r = xplane.reduce(xplane.load(path))
    assert r["chips"] == 1
    launch = r["launches"]["jit_fused_sample_chunk_fn"]
    assert launch["count"] == 7
    assert launch["median_s"] == pytest.approx(6.384e-3, rel=1e-3)
    assert 0 < r["busy_s"] <= r["window_s"] and r["window_s"] == pytest.approx(0.0383, rel=1e-2)
    assert r["busy_s"] / r["window_s"] > 0.99
    names = [n for n, _ in r["device_ops"]]
    assert names[0].startswith("fused_sample_chunk_fn") and len(names) == 10
    assert all(len(n) < 64 for n in names)  # the op's name, not its whole HLO text
    pct, bound = flops.roofline_pct(DDPG_WORK(), 800, launch["median_s"], peaks.lookup("TPU v5 lite"))
    assert pct == pytest.approx(6.41, rel=1e-2) and bound == "flops"

