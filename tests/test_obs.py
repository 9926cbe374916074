"""Telemetry-plane tests (obs/; docs/OBSERVABILITY.md §4).

Four tiers:
  - unit: the /healthz state machine (named degraded conditions, the
    draining latch, read-time probes), Prometheus rendering (family
    grouping, bool coercion, the health trio), the live exporter's three
    endpoints over real HTTP, straggler detection, the pod aggregator's
    record reduction, the run-start header record, and the clock-aligned
    merge-trace fuser.
  - guards: the hot-path overhead pin (MetricsLogger.log under a live
    scraper stays <2% of a realistic chunk body — the same discipline as
    test_trace.py's span guard) and the SIGUSR2 / watchdog-stall trace
    export paths.
  - schema drift (ISSUE 18 satellite): a real CPU train run's emitted
    JSONL keys must all appear in docs/OBSERVABILITY.md, AND every
    pod_*/serve_*/fused_* field the docs tables promise must actually be
    emitted by the corresponding Stats snapshot / pod record.
  - 2-process gloo drill (slow; OBS_FULL=1 in scripts/obs_smoke.sh): live
    /metrics scrape showing pod spread keys, a faults.py peer loss
    flipping /healthz healthy->degraded on the survivor, both processes
    exiting EXIT_POD_DEGRADED, and merge-trace fusing both hosts' trace
    files into one clock-aligned Perfetto timeline.
"""

import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.metrics import (
    FusedBeatStats,
    MetricsLogger,
    PodStats,
    ServeStats,
)
from distributed_ddpg_tpu.obs import (
    ObsExporter,
    PodAggregator,
    detect_straggler,
    health,
    render_prometheus,
)
from distributed_ddpg_tpu.obs import aggregate

CHILD = Path(__file__).parent / "multihost_child.py"
REPO = str(CHILD.parent.parent)
DOCS = Path(REPO) / "docs" / "OBSERVABILITY.md"


@pytest.fixture(autouse=True)
def _clean_singletons():
    """The health singleton and the trace ring are process-wide; a test
    that latches `draining` or enables the recorder must not leak either
    into its neighbors."""
    health.get().reset()
    yield
    health.get().reset()
    trace.disable()


def _http(url: str, timeout: float = 5.0):
    """(status, content_type, body) — 4xx/5xx return, they don't raise."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.headers.get("Content-Type", ""), \
                r.read().decode("utf-8")
    except urllib.error.HTTPError as e:
        return e.code, e.headers.get("Content-Type", ""), \
            e.read().decode("utf-8")


# --------------------------------------------------------------------------
# health state machine (obs/health.py)
# --------------------------------------------------------------------------


def test_health_starts_healthy():
    state, reasons = health.get().state()
    assert state == health.HEALTHY and reasons == []
    snap = health.get().snapshot()
    assert snap["state"] == "healthy" and snap["code"] == 0
    assert snap["reasons"] == []
    assert snap["t_unix"] >= snap["since_unix"]


def test_health_note_sets_and_clears_degraded():
    h = health.get()
    h.note("pod_state_degraded")
    assert h.state() == (health.DEGRADED, ["pod_state_degraded"])
    h.note("guardrail_quarantine")
    assert h.state()[1] == ["guardrail_quarantine", "pod_state_degraded"]
    # Reversible: the elastic pod growing back clears its condition.
    h.note("pod_state_degraded", active=False)
    h.note("guardrail_quarantine", active=False)
    assert h.state() == (health.HEALTHY, [])


def test_health_drain_latches_first_reason():
    h = health.get()
    h.drain("watchdog stall: no trainer progress for 60s")
    h.drain("preempted (SIGTERM)")  # later churn must not overwrite
    h.note("pod_state_degraded")    # draining dominates conditions
    state, reasons = h.state()
    assert state == health.DRAINING
    assert reasons == ["watchdog stall: no trainer progress for 60s"]
    assert h.snapshot()["code"] == 2


def test_health_probe_evaluated_at_read_time():
    h = health.get()
    flag = [False]
    h.register_probe("serve_overloaded", lambda: flag[0])
    assert h.state()[0] == health.HEALTHY
    flag[0] = True  # no note() call: the probe alone must flip the state
    assert h.state() == (health.DEGRADED, ["serve_overloaded"])
    flag[0] = False
    assert h.state()[0] == health.HEALTHY


def test_health_raising_probe_reads_probe_error():
    h = health.get()
    h.register_probe("serve_overloaded", lambda: 1 / 0)
    state, reasons = h.state()
    # "Cannot determine health" must gate exactly like "unhealthy".
    assert state == health.DEGRADED
    assert reasons == ["serve_overloaded:probe_error"]


def test_health_reset_returns_fresh():
    h = health.get()
    h.note("x")
    h.drain("terminal")
    h.register_probe("p", lambda: True)
    h.reset()
    assert h.state() == (health.HEALTHY, [])


# --------------------------------------------------------------------------
# Prometheus rendering (obs/exporter.py)
# --------------------------------------------------------------------------


def test_render_prometheus_families_not_interleaved():
    latest = {
        "train": {"kind": "train", "learner_steps_per_sec": 42.5,
                  "pod_beats": 7},
        "pod": {"kind": "pod", "learner_steps_per_sec": 1.5},
    }
    text = render_prometheus(latest, {"t_unix_base": 123.5}, health.get())
    assert 'ddpg_learner_steps_per_sec{kind="train"} 42.5' in text
    assert 'ddpg_learner_steps_per_sec{kind="pod"} 1.5' in text
    assert "ddpg_t_unix_base 123.5" in text
    # Exposition format: ONE TYPE line per family, samples contiguous.
    lines = text.strip().splitlines()
    assert lines.count("# TYPE ddpg_learner_steps_per_sec gauge") == 1
    current = None
    for ln in lines:
        if ln.startswith("# TYPE "):
            current = ln.split()[2]
            continue
        assert current is not None and ln.startswith(current), (
            f"sample {ln!r} outside its family block ({current})"
        )


def test_render_prometheus_values_and_sanitization():
    latest = {"train": {
        "kind": "train",
        "flag": True,          # bool -> 1
        "note": "a string",    # unexportable: dropped
        "nested": {"a": 1},    # unexportable: dropped
        "weird-key:1": 3.0,    # sanitized name
    }}
    text = render_prometheus(latest)
    assert 'ddpg_flag{kind="train"} 1' in text
    assert "a string" not in text and "nested" not in text
    assert 'ddpg_weird_key_1{kind="train"} 3' in text


def test_render_prometheus_health_trio():
    health.get().note("pod_state_degraded")
    text = render_prometheus(None, None, health.get())
    assert "ddpg_health_code 1" in text
    assert 'ddpg_health{state="degraded"} 1' in text
    assert 'ddpg_health{state="healthy"} 0' in text
    assert 'ddpg_health{state="draining"} 0' in text


# --------------------------------------------------------------------------
# live ingress endpoints (obs/exporter.py over real HTTP)
# --------------------------------------------------------------------------


def test_exporter_endpoints(tmp_path):
    h = health.get()
    latest = {"train": {"kind": "train", "learner_steps_per_sec": 42.5}}
    ex = ObsExporter(
        0,  # ephemeral: tests must not fight over a fixed port
        health=h,
        latest_fn=lambda: latest,
        counters_fn=lambda: {"t_unix_base": 5.25},
        trace_dir=str(tmp_path),
    ).start()
    try:
        assert ex.port > 0
        code, ctype, body = _http(ex.url("/metrics"))
        assert code == 200 and "version=0.0.4" in ctype
        assert 'ddpg_learner_steps_per_sec{kind="train"} 42.5' in body
        assert "ddpg_t_unix_base 5.25" in body
        assert "ddpg_obs_scrapes_total" in body
        assert f"ddpg_pid {os.getpid()}" in body

        code, ctype, body = _http(ex.url("/healthz"))
        assert code == 200 and ctype.startswith("application/json")
        assert json.loads(body)["state"] == "healthy"

        h.note("pod_state_degraded")
        code, _, body = _http(ex.url("/healthz"))
        snap = json.loads(body)
        assert code == 503 and snap["state"] == "degraded"
        assert snap["reasons"] == ["pod_state_degraded"]
        h.note("pod_state_degraded", active=False)
        assert _http(ex.url("/healthz"))[0] == 200

        h.drain("preempted (SIGTERM)")
        code, _, body = _http(ex.url("/healthz"))
        assert code == 503 and json.loads(body)["state"] == "draining"

        code, _, body = _http(ex.url("/nope"))
        assert code == 404 and "/metrics /healthz /trace" in body

        # The scrape counter is itself scraped (previous scrapes counted).
        _, _, body = _http(ex.url("/metrics"))
        m = re.search(r"ddpg_obs_scrapes_total (\d+)", body)
        assert m and int(m.group(1)) >= 1
    finally:
        ex.stop()


def test_exporter_trace_endpoint(tmp_path):
    ex = ObsExporter(0, trace_dir=str(tmp_path)).start()
    try:
        _, _, body = _http(ex.url("/trace"))
        assert json.loads(body) == {"enabled": False, "events": 0}

        trace.configure(capacity=64)
        with trace.span("live_work"):
            pass
        _, _, body = _http(ex.url("/trace"))
        obj = json.loads(body)
        assert obj["enabled"] is True and obj["events"] >= 1
        assert obj["path"] == os.path.join(str(tmp_path),
                                           "trace_ondemand.json")
        doc = json.loads(Path(obj["path"]).read_text())
        assert any(e.get("name") == "live_work" for e in doc["traceEvents"])
    finally:
        ex.stop()


def test_exporter_counters_fn_failure_degrades_to_basics():
    ex = ObsExporter(0, counters_fn=lambda: 1 / 0).start()
    try:
        code, _, body = _http(ex.url("/metrics"))
        assert code == 200 and "ddpg_pid" in body  # basics survive
    finally:
        ex.stop()


def test_exporter_bind_conflict_raises_oserror():
    """train.py downgrades a taken port to a warning — the typed failure
    it catches is OSError from start()."""
    ex = ObsExporter(0).start()
    try:
        with pytest.raises(OSError):
            ObsExporter(ex.port).start()
    finally:
        ex.stop()


# --------------------------------------------------------------------------
# hot-path overhead guard (the telemetry plane must stay off the hot path)
# --------------------------------------------------------------------------


def test_obs_logging_overhead_under_2_percent():
    """MetricsLogger.log (the ONLY train-loop cost the ingress adds — the
    exporter renders on the scrape thread) must cost <2% of a realistic
    chunk body, WHILE a scraper hammers /metrics. Costs measured
    separately min-over-repeats, the test_trace.py discipline: a
    subtraction of two noisy ~20ms wall timings would flake on scheduler
    jitter."""
    log = MetricsLogger("", echo=False)
    ex = ObsExporter(0, latest_fn=log.latest).start()
    stop = threading.Event()

    def scraper():
        while not stop.is_set():
            try:
                _http(ex.url("/metrics"), timeout=2.0)
            except OSError:
                pass

    t = threading.Thread(target=scraper, daemon=True)
    t.start()
    a = np.random.default_rng(0).standard_normal((160, 160)).astype(np.float32)
    try:
        def log_cost_s() -> float:
            n = 5_000
            t0 = time.perf_counter()
            for i in range(n):
                log.log("train", i, learner_steps_per_sec=42.5,
                        critic_loss=0.1, buffer_fill=0.5)
            return (time.perf_counter() - t0) / n

        def body_cost_s() -> float:
            n = 50
            t0 = time.perf_counter()
            for _ in range(n):
                x = a
                for _ in range(6):
                    x = x @ a
            return (time.perf_counter() - t0) / n

        log_cost_s(), body_cost_s()  # warm pools + code paths
        cost = min(log_cost_s() for _ in range(3))
        body = min(body_cost_s() for _ in range(5))
        overhead = cost / body
        assert overhead < 0.02, (
            f"obs logging overhead {overhead:.2%} "
            f"(log {cost * 1e6:.2f}us vs body {body * 1e6:.1f}us)"
        )
    finally:
        stop.set()
        t.join(timeout=5)
        ex.stop()


# --------------------------------------------------------------------------
# straggler detection + pod aggregation (obs/aggregate.py)
# --------------------------------------------------------------------------


def test_detect_straggler_two_hosts_relative_test():
    # 2-host pods pin z-scores at +/-1: the relative test must carry.
    assert detect_straggler([10.0, 30.0]) == 1
    assert detect_straggler([30.0, 10.0]) == 0
    assert detect_straggler([10.0, 11.0]) == -1  # inside rel_thresh


def test_detect_straggler_zscore_population():
    assert detect_straggler([10.0, 10.0, 10.0, 100.0]) == 3
    assert detect_straggler([10.0, 10.0, 10.0, 10.0]) == -1
    assert detect_straggler([10.0, 11.0, 9.0, 10.5]) == -1


def test_detect_straggler_absolute_floor_and_degenerate_inputs():
    # 3x ratio but microsecond scale: the min_abs_ms floor must gate it.
    assert detect_straggler([0.1, 0.3]) == -1
    assert detect_straggler([0.1, 0.3], min_abs_ms=0.1) == 1
    assert detect_straggler([5.0]) == -1
    assert detect_straggler([]) == -1


def test_pod_aggregator_single_host_returns_none():
    agg = PodAggregator(gather_fn=lambda vec: vec.reshape(1, -1))
    assert agg.collect(beats=10, ingest_rows=100) is None


def test_pod_aggregator_reduces_and_attributes():
    gathered = np.zeros((2, aggregate.SLOTS), np.int64)
    # host 0: beat 10ms, 5 rows/s, backlog 0;  host 1: beat 500ms,
    # 4 rows/s, backlog 2; clocks 250ms apart. Slots are milli-scaled.
    gathered[0] = [10_000, 5_000, 0, 1_000_000]
    gathered[1] = [500_000, 4_000, 2_000, 1_000_250]
    stats = PodStats()
    agg = PodAggregator(gather_fn=lambda vec: gathered, stats=stats)
    rec = agg.collect(beats=50, ingest_rows=1000, transfer_backlog=0)
    assert rec["pod_agg_hosts"] == 2
    assert rec["pod_beat_ms_min"] == 10.0
    assert rec["pod_beat_ms_max"] == 500.0
    assert rec["pod_beat_ms_spread"] == 490.0
    assert rec["pod_ingest_rows_per_s_min"] == 4.0
    assert rec["pod_ingest_rows_per_s_max"] == 5.0
    assert rec["pod_ingest_rows_per_s_spread"] == 1.0
    assert rec["pod_transfer_backlog_max"] == 2.0
    assert rec["pod_clock_spread_ms"] == 250.0
    assert rec["pod_straggler_host"] == 1
    snap = stats.snapshot()
    assert snap["pod_stragglers"] == 1
    assert snap["pod_straggler_host"] == 1


def test_pod_aggregator_sample_rates_are_interval_scoped():
    agg = PodAggregator(gather_fn=lambda v: v.reshape(1, -1))
    agg.sample(beats=0, ingest_rows=0, transfer_backlog=0)
    time.sleep(0.05)
    vec = agg.sample(beats=10, ingest_rows=500, transfer_backlog=3)
    # 10 beats over ~50ms -> ~5ms/beat; backlog is a plain gauge.
    assert 1_000 <= vec[aggregate.SLOT_BEAT_MS] <= 50_000
    assert vec[aggregate.SLOT_TRANSFER_BACKLOG] == 3_000
    assert vec[aggregate.SLOT_INGEST_RATE] > 0


# --------------------------------------------------------------------------
# run-start header record (MetricsLogger; ISSUE 18 satellite)
# --------------------------------------------------------------------------


def test_metrics_logger_writes_header_with_unix_base(tmp_path):
    path = tmp_path / "run.jsonl"
    log = MetricsLogger(str(path), echo=False)
    first = json.loads(path.read_text().splitlines()[0])
    assert first["kind"] == "header"
    assert first["t_unix_base"] == log.t_unix_base
    assert abs(log.t_unix_base - time.time()) < 60.0
    assert first["pid"] == os.getpid()
    log.log("train", 5, learner_steps_per_sec=1.0)
    latest = log.latest()
    assert set(latest) == {"header", "train"}
    # wall_time stays RELATIVE; the header's absolute base anchors it.
    assert latest["train"]["wall_time"] < 60.0


# --------------------------------------------------------------------------
# drain paths: watchdog stall + SIGUSR2 export
# --------------------------------------------------------------------------


def test_watchdog_stall_drains_health():
    from distributed_ddpg_tpu.watchdog import Watchdog

    fired = threading.Event()
    wd = Watchdog(0.3, progress=lambda: 0, on_stall=fired.set,
                  stall_dir=None).start()
    try:
        assert fired.wait(timeout=10.0), "watchdog never fired"
        state, reasons = health.get().state()
        # /healthz must already read terminal while artifacts are written.
        assert state == health.DRAINING
        assert reasons and "watchdog stall" in reasons[0]
    finally:
        wd.stop()


def test_pod_abort_linger_serves_latched_draining_verdict():
    """ISSUE 19 satellite: during the pod-abort linger window (rank 0
    keeps its ingress up briefly so one last scrape can read the
    verdict), /healthz must return 503 with state `draining` and the
    LATCHED degraded reason — not a fresh `healthy`. The drain handshake
    is train.drain_for_pod_exit, factored out of pod_degraded_exit so
    this contract is testable without os._exit."""
    from distributed_ddpg_tpu import train

    health.get().note("pod peer lost: process 1")
    ex = ObsExporter(0).start()
    try:
        train.drain_for_pod_exit(train.EXIT_POD_SHRINK)
        code, _, body = _http(ex.url("/healthz"))
        assert code == 503
        snap = json.loads(body)
        assert snap["state"] == "draining"
        assert any("pod peer lost" in r for r in snap["reasons"])
        # Latched: a later recovery signal must NOT un-drain the verdict.
        health.get().note("pod peer lost: process 1", active=False)
        code, _, body = _http(ex.url("/healthz"))
        assert code == 503
        assert json.loads(body)["state"] == "draining"
    finally:
        ex.stop()


def test_drain_for_pod_exit_without_prior_reason_names_the_code():
    from distributed_ddpg_tpu import train

    train.drain_for_pod_exit(train.EXIT_POD_DEGRADED)
    state, reasons = health.get().state()
    assert state == health.DRAINING
    assert reasons == ["pod abort (exit 76)"]


@pytest.mark.skipif(not hasattr(signal, "SIGUSR2"),
                    reason="platform has no SIGUSR2")
def test_sigusr2_reexports_live_trace(tmp_path):
    prev = signal.getsignal(signal.SIGUSR2)
    path = tmp_path / "live" / "trace.json"
    try:
        trace.configure(capacity=128)
        assert trace.install_signal_export(str(path)) is True
        with trace.span("before_poke"):
            pass
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.monotonic() + 5.0
        while not path.exists() and time.monotonic() < deadline:
            time.sleep(0.05)
        doc = json.loads(path.read_text())
        assert any(
            e.get("name") == "before_poke" for e in doc["traceEvents"]
        )
    finally:
        signal.signal(signal.SIGUSR2, prev)


@pytest.mark.skipif(not hasattr(signal, "SIGUSR2"),
                    reason="platform has no SIGUSR2")
def test_install_signal_export_refuses_off_main_thread(tmp_path):
    out = []
    t = threading.Thread(
        target=lambda: out.append(
            trace.install_signal_export(str(tmp_path / "t.json"))
        )
    )
    t.start()
    t.join()
    assert out == [False]


# --------------------------------------------------------------------------
# merge-trace (tools/runs.py): clock-aligned pod timelines
# --------------------------------------------------------------------------


def _fake_host_trace(path, *, wall_t0, offset_ms, process_index, pid,
                     span_ts):
    doc = {
        "traceEvents": [
            {"name": "beat", "ph": "X", "pid": pid, "tid": 1,
             "ts": span_ts, "dur": 500, "args": {}},
            # trace.py metadata events carry NO ts — the merge must cope.
            {"name": "thread_name", "ph": "M", "pid": pid, "tid": 1,
             "args": {"name": "learner"}},
        ],
        "otherData": {"wall_t0": wall_t0, "pid": pid,
                      "process_index": process_index,
                      "clock_offset_ms": offset_ms},
    }
    path.write_text(json.dumps(doc))
    return path


def test_merge_traces_aligns_clocks_and_remaps_pids(tmp_path):
    from distributed_ddpg_tpu.tools.runs import merge_traces

    a = _fake_host_trace(tmp_path / "h0.json", wall_t0=1000.0,
                         offset_ms=0.0, process_index=0, pid=111,
                         span_ts=1000)
    # Host 1's recorder started 200ms later on a clock the handshake
    # measured 250ms AHEAD: its aligned anchor (999.95) is the earliest.
    b = _fake_host_trace(tmp_path / "h1.json", wall_t0=1000.2,
                         offset_ms=250.0, process_index=1, pid=222,
                         span_ts=1000)
    out = tmp_path / "merged.json"
    n_events, n_hosts = merge_traces([str(a), str(b)], str(out))
    assert n_hosts == 2
    doc = json.loads(out.read_text())
    spans = {e["pid"]: e for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert set(spans) == {0, 1}  # original pids remapped to host index
    # Host 0 shifts +50ms onto the common base; host 1 anchors it.
    assert spans[0]["ts"] == pytest.approx(51_000.0)
    assert spans[1]["ts"] == pytest.approx(1_000.0)
    pnames = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
              if e.get("name") == "process_name"}
    assert "host0 pid=111" in pnames[0] and "host1 pid=222" in pnames[1]
    sort_idx = {e["pid"]: e["args"]["sort_index"]
                for e in doc["traceEvents"]
                if e.get("name") == "process_sort_index"}
    assert sort_idx == {0: 0, 1: 1}
    assert doc["otherData"]["merged_from"] == [str(a), str(b)]
    assert doc["otherData"]["t_unix_base"] == pytest.approx(999.95)
    assert n_events == len(doc["traceEvents"])


def test_merge_traces_foreign_file_and_errors(tmp_path):
    from distributed_ddpg_tpu.tools.runs import merge_traces

    # A foreign Chrome trace (no otherData): host = file order, no shift.
    foreign = tmp_path / "foreign.json"
    foreign.write_text(json.dumps({"traceEvents": [
        {"name": "x", "ph": "X", "pid": 9, "tid": 1, "ts": 5, "dur": 1},
    ]}))
    out = tmp_path / "m.json"
    n_events, n_hosts = merge_traces([str(foreign)], str(out))
    assert n_hosts == 1
    doc = json.loads(out.read_text())
    span = [e for e in doc["traceEvents"] if e.get("ph") == "X"][0]
    assert span["pid"] == 0 and span["ts"] == 5

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"not_a_trace": True}))
    with pytest.raises(ValueError):
        merge_traces([str(bad)], str(out))


def test_merge_trace_cli(tmp_path):
    a = _fake_host_trace(tmp_path / "h0.json", wall_t0=10.0, offset_ms=0.0,
                         process_index=0, pid=1, span_ts=0)
    b = _fake_host_trace(tmp_path / "h1.json", wall_t0=10.0, offset_ms=0.0,
                         process_index=1, pid=2, span_ts=0)
    out = tmp_path / "pod.json"
    res = subprocess.run(
        [sys.executable, "-m", "distributed_ddpg_tpu.tools.runs",
         "merge-trace", str(a), str(b), "--out", str(out)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert res.returncode == 0, res.stderr
    assert "2 host trace(s)" in res.stdout
    assert {e["pid"] for e in json.loads(out.read_text())["traceEvents"]} \
        == {0, 1}
    # Unreadable input: exit 1, not a traceback.
    res = subprocess.run(
        [sys.executable, "-m", "distributed_ddpg_tpu.tools.runs",
         "merge-trace", str(tmp_path / "missing.json"),
         "--out", str(out)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert res.returncode == 1


# --------------------------------------------------------------------------
# tools.runs: TPU-probe failure tails are skipped (ISSUE 18 satellite)
# --------------------------------------------------------------------------


def test_summarize_skips_probe_failure_tails(tmp_path, capsys):
    from distributed_ddpg_tpu.tools.runs import summarize_run

    path = tmp_path / "r.jsonl"
    good = {"kind": "train", "step": 100, "wall_time": 1.0,
            "learner_steps_per_sec": 100.0}
    path.write_text(
        json.dumps(good) + "\n"
        + json.dumps({**good, "step": 200, "wall_time": 2.0}) + "\n"
        # A probe-failure record (older ones carry CPU-fallback numbers),
        # the failure recorded as a structured field — its numbers must
        # not poison the digest or any A/B against a healthy baseline.
        + json.dumps({"kind": "train", "step": 300, "wall_time": 3.0,
                      "learner_steps_per_sec": 1.0,
                      "tpu_error": "probe timeout"}) + "\n"
    )
    digest = summarize_run(str(path))
    assert digest["records"]["train"] == 2
    assert digest["metrics"]["learner_steps_per_sec"]["last"] == 100.0
    err = capsys.readouterr().err
    assert "skipped 1 record" in err and "TPU-probe failure" in err


def test_compare_inherits_probe_failure_skip(tmp_path):
    from distributed_ddpg_tpu.tools.runs import compare_runs

    rec = {"kind": "train", "step": 100, "wall_time": 1.0,
           "learner_steps_per_sec": 100.0}
    a = tmp_path / "a.jsonl"
    a.write_text(json.dumps(rec) + "\n")
    b = tmp_path / "b.jsonl"
    b.write_text(
        json.dumps(rec) + "\n"
        + json.dumps({**rec, "step": 200, "learner_steps_per_sec": 1.0,
                      "probe_error": "selftest timeout"}) + "\n"
    )
    text, rows = compare_runs(str(a), str(b))
    lsps = [r for r in rows if r[0] == "learner_steps_per_sec"]
    # The fallback record dropped: no phantom 99% regression.
    assert lsps and lsps[0][1] == lsps[0][2] == 100.0, rows


# --------------------------------------------------------------------------
# schema drift (ISSUE 18 satellite): docs tables <-> emitted keys
# --------------------------------------------------------------------------


def _documented_family_keys(prefixes):
    """Backticked field tokens from the FIELDS column of every 3-column
    docs/OBSERVABILITY.md table row, slash-groups expanded — the same
    shorthand the ObservabilityDrift lint reads."""
    from distributed_ddpg_tpu.analysis.rules import _expand_slash

    keys = set()
    for line in DOCS.read_text().splitlines():
        s = line.strip()
        if not s.startswith("|"):
            continue
        cells = [c.strip() for c in s.strip("|").split("|")]
        if len(cells) < 3 or set(cells[0]) <= {"-", " "}:
            continue
        for tok in re.findall(r"`([^`]*)`", cells[1]):
            for sub in re.findall(r"[a-z][a-z0-9_/<>]*", tok):
                for k in _expand_slash(sub):
                    if "<" not in k and k.startswith(prefixes):
                        keys.add(k)
    return keys


def test_documented_pod_serve_fused_keys_are_emitted():
    """Direction 2 of the drift pin: every `pod_*`/`serve_*`/`fused_*`
    field the docs tables promise must actually exist in the emitted key
    universe — a doc row for a renamed/removed field is a lie operators
    will alert on."""
    emitted = set(PodStats().snapshot())
    emitted |= set(ServeStats().snapshot())
    emitted |= set(FusedBeatStats().snapshot())
    gathered = np.zeros((2, aggregate.SLOTS), np.int64)
    gathered[1, aggregate.SLOT_BEAT_MS] = 100_000
    emitted |= set(PodAggregator(gather_fn=lambda v: gathered)
                   .collect(beats=1, ingest_rows=1))
    # serve_client_fallbacks is emitted by the actor pool, not ServeStats;
    # pin it to its emitting source so it can't silently vanish either.
    pool_src = (Path(REPO) / "distributed_ddpg_tpu" / "actors"
                / "pool.py").read_text()
    emitted |= {k for k in ("serve_client_fallbacks",)
                if f'"{k}"' in pool_src}

    documented = _documented_family_keys(("pod_", "serve_", "fused_"))
    assert documented, "no pod_/serve_/fused_ fields found in docs tables"
    phantom = sorted(documented - emitted)
    assert not phantom, (
        f"docs/OBSERVABILITY.md documents fields nothing emits: {phantom}"
    )


def test_train_run_keys_are_documented(tmp_path):
    """Direction 1: a real CPU train run's JSONL keys must ALL appear in
    docs/OBSERVABILITY.md (matched with the ObservabilityDrift lint's own
    token/template semantics). Doubles as the end-to-end --obs_port pin:
    a live scraper thread must see the header base and /healthz 200 while
    the run is in flight."""
    from distributed_ddpg_tpu.analysis.rules import (
        _doc_field_patterns,
        _doc_mentions,
        _expand_slash,
    )
    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.train import train_jax

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    seen = {"metrics": None, "healthz": None}
    stop = threading.Event()

    def scraper():
        while not stop.is_set():
            try:
                code, _, body = _http(
                    f"http://127.0.0.1:{port}/metrics", timeout=2.0)
                if code == 200 and "ddpg_t_unix_base" in body:
                    seen["metrics"] = body
                code, _, body = _http(
                    f"http://127.0.0.1:{port}/healthz", timeout=2.0)
                if code == 200:
                    seen["healthz"] = json.loads(body)
                if seen["metrics"] is not None and seen["healthz"] is not None:
                    return
            except OSError:
                pass
            stop.wait(0.3)

    t = threading.Thread(target=scraper, daemon=True)
    t.start()
    log_path = tmp_path / "train.jsonl"
    cfg = DDPGConfig(
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        num_actors=1,
        # test_trace.py's sizing: paced ingest carries the budget past the
        # 50-chunk log cadence so at least one train record lands.
        total_env_steps=4_000,
        replay_min_size=1_500,
        replay_capacity=16_384,
        max_ingest_ratio=6.0,
        eval_every=600,
        eval_episodes=1,
        obs_port=port,
        log_path=str(log_path),
    )
    try:
        out = train_jax(cfg)
    finally:
        stop.set()
        t.join(timeout=10)
    assert out["learner_steps"] > 0

    assert seen["metrics"] is not None, "scraper never reached /metrics"
    assert seen["healthz"] is not None, "scraper never saw /healthz 200"
    assert seen["healthz"]["state"] == "healthy"

    doc_text = DOCS.read_text()
    plain = {
        t2 for tok in re.findall(r"[a-z][a-z0-9_/<>]*", doc_text)
        for t2 in _expand_slash(tok) if "<" not in t2
    }
    patterns = _doc_field_patterns(doc_text)
    records = [json.loads(ln) for ln in log_path.read_text().splitlines()]
    kinds = {r["kind"] for r in records}
    assert "header" in kinds and "train" in kinds and "final" in kinds
    # Run facts (train.run_facts): the header and final records and the
    # returned summary all say what the run ran on and which learner leg.
    for rec in (records[0], records[-1], out):
        assert rec["platform"] == "cpu" and rec["device_kind"] == "cpu"
        assert rec["n_devices"] == rec["state_devices"] == 8
        assert rec["replay_devices"] == 8
        assert rec["learner_chunk"] == 8
        assert rec["fused_chunk_active"] is False
        assert rec["kernel_state_tiles"] is None  # the scan leg holds no tiles
    assert records[0]["kind"] == "header" and records[-1]["kind"] == "final"
    # The mesh's chunk executable holds collectives, counted once it has run
    # (the header is written before the first launch); XLA:CPU is handed no
    # compile option and writes none of them asynchronous.
    assert records[0]["chunk_collectives"] is None
    for rec in (records[-1], out):
        assert rec["chunk_collectives"]["instructions"] >= 1
        assert rec["chunk_collectives"]["asynchronous"] == 0
    assert np.isfinite(records[-1]["critic_loss"])
    assert records[-1]["first_chunk_s"] > 0 and records[-1]["steady_s"] > 0
    undocumented = sorted({
        key
        for r in records
        for key in r
        if not _doc_mentions(key, plain, patterns)
    })
    assert not undocumented, (
        f"run emitted keys docs/OBSERVABILITY.md never mentions: "
        f"{undocumented}"
    )


def test_clock_handshake_single_process_is_none():
    from distributed_ddpg_tpu.parallel import multihost

    assert multihost.clock_handshake() is None


# --------------------------------------------------------------------------
# 2-process gloo drill (slow): live scrape, peer loss, merged timeline
# --------------------------------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _free_port_pair() -> int:
    """Base port with base+1 also free (child obs port = base + pid)."""
    for _ in range(20):
        with socket.socket() as a:
            a.bind(("127.0.0.1", 0))
            base = a.getsockname()[1]
            if base + 1 > 65_535:
                continue
            with socket.socket() as b:
                try:
                    b.bind(("127.0.0.1", base + 1))
                except OSError:
                    continue
                return base
    raise RuntimeError("no adjacent free port pair")


def _infra_flake(results) -> bool:
    """The known multiprocess-CPU gloo stream race (see test_pod.py's
    twin): any SIGABRT / gloo EnforceNotMet marks the launch infra-torn,
    not a verdict on the contract under test."""
    return any(
        rc == -signal.SIGABRT
        or "gloo::EnforceNotMet" in out
        or "Gloo all-reduce failed" in out
        for rc, out in results
    )


def _try_http(url: str):
    try:
        return _http(url, timeout=2.0)
    except OSError:
        return None  # not up yet / already gone


def _obs_drill(base: Path):
    """Launch the 2-process pod with the ingress + per-process traces
    armed and process 1 scripted to freeze at its 55th steady-state beat
    (past the 50-chunk cadence, so rank 0's pod record exists). The
    parent live-polls proc0's /metrics and /healthz throughout. Returns
    ([(rc, out)] per process, observations dict)."""
    base.mkdir(parents=True, exist_ok=True)
    log_dir = base / "logs"
    log_dir.mkdir()
    trace_root = base / "traces"
    trace_root.mkdir()
    obs_base = _free_port_pair()
    child_env = {
        **os.environ,
        "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        # The pod deadline must win against the runtime's own heartbeat
        # killer (same rationale as test_pod.py).
        "POD_RUNTIME_HEARTBEAT_TIMEOUT_S": "300",
        # hang, not kill: both processes must run their abort path and
        # EXPORT their trace rings for the merge assertion. Background
        # beats so the hung process's own frozen beat is bounded by its
        # lockstep-lane deadline (the test_pod.py hang-drill shape).
        "POD_FAULTS": "pod:1:hang@55~600",
        "POD_TIMEOUT_S": "6",
        "POD_STARTUP_GRACE_S": "120",
        "POD_CKPT_DIR": "",
        "POD_LOG_DIR": str(log_dir),
        "POD_TOTAL_STEPS": "500000",
        "POD_BG_SYNC": "1",
        "POD_OBS_PORT_BASE": str(obs_base),
        "POD_TRACE_DIR": str(trace_root),
    }
    coord = _free_port()
    procs = [
        subprocess.Popen(
            [sys.executable, str(CHILD), str(pid), "2", str(coord),
             "podtrain"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=REPO, env=child_env,
        )
        for pid in range(2)
    ]
    seen = {"metrics_up": False, "healthy_seen": False, "spread": None,
            "agg_hosts": None, "degraded_json": None}
    deadline = time.monotonic() + 360.0
    try:
        while time.monotonic() < deadline:
            if all(p.poll() is not None for p in procs):
                break
            got = _try_http(f"http://127.0.0.1:{obs_base}/metrics")
            if got is not None and got[0] == 200:
                seen["metrics_up"] = True
                body = got[2]
                m = re.search(
                    r'ddpg_pod_beat_ms_spread\{kind="pod"\} '
                    r'([0-9.eE+-]+)', body)
                if m:
                    seen["spread"] = float(m.group(1))
                m = re.search(
                    r'ddpg_pod_agg_hosts\{kind="pod"\} ([0-9.eE+-]+)',
                    body)
                if m:
                    seen["agg_hosts"] = float(m.group(1))
            got = _try_http(f"http://127.0.0.1:{obs_base}/healthz")
            if got is not None:
                code, _, body = got
                try:
                    snap = json.loads(body)
                except ValueError:
                    snap = None
                if snap is not None:
                    if code == 200 and snap.get("state") == "healthy":
                        seen["healthy_seen"] = True
                    elif code == 503:
                        seen["degraded_json"] = snap
            time.sleep(0.25)
    finally:
        results = []
        for p in procs:
            try:
                out, _ = p.communicate(
                    timeout=max(5.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
            results.append((p.returncode, out))
    return results, seen


@pytest.mark.slow
def test_two_process_scrape_peer_loss_and_merged_timeline(tmp_path):
    """ISSUE 18 acceptance drill: a 2-process CPU pod serving live
    ingress shows the pod spread keys on rank 0's /metrics, flips
    /healthz healthy -> degraded when a scripted faults.py peer freeze
    declares peer loss, exits EXIT_POD_DEGRADED on both processes, and
    merge-trace fuses both hosts' trace files into one clock-aligned
    timeline with a process track per host."""
    from distributed_ddpg_tpu.tools.runs import merge_traces
    from distributed_ddpg_tpu.train import EXIT_POD_DEGRADED

    results = seen = base = None
    for attempt in range(3):
        base = tmp_path / f"attempt{attempt}"
        results, seen = _obs_drill(base)
        if not _infra_flake(results):
            break
    (rc0, out0), (rc1, out1) = results
    assert rc0 == EXIT_POD_DEGRADED, f"proc0 rc={rc0}\n{out0}"
    assert rc1 == EXIT_POD_DEGRADED, f"proc1 rc={rc1}\n{out1}"
    for out in (out0, out1):
        assert "pod peer lost" in out, out
        assert "degraded=1" in out, out

    # --- live-scrape observations (collected DURING the run) ---
    assert seen["metrics_up"], seen
    assert seen["healthy_seen"], seen
    assert seen["agg_hosts"] == 2.0, seen
    assert seen["spread"] is not None and seen["spread"] >= 0.0, seen
    snap = seen["degraded_json"]
    assert snap is not None, f"/healthz never flipped\n{out0}"
    assert snap["state"] in ("degraded", "draining"), snap
    assert any("pod_peer_lost" in r for r in snap["reasons"]), snap

    # The pod record also landed in rank 0's JSONL stream.
    recs = [
        json.loads(ln)
        for ln in (base / "logs" / "proc0.jsonl").read_text().splitlines()
        if ln.startswith("{")
    ]
    pods = [r for r in recs if r.get("kind") == "pod"]
    assert pods, "rank 0 logged no pod record"
    assert all("pod_beat_ms_spread" in r for r in pods)
    assert {r["pod_agg_hosts"] for r in pods} == {2}

    # --- merged pod timeline ---
    t0p = base / "traces" / "proc0" / "trace.json"
    t1p = base / "traces" / "proc1" / "trace.json"
    assert t0p.exists(), f"proc0 exported no trace\n{out0}"
    assert t1p.exists(), f"proc1 exported no trace\n{out1}"
    merged = base / "trace_merged.json"
    n_events, n_hosts = merge_traces([str(t0p), str(t1p)], str(merged))
    assert n_hosts == 2 and n_events > 0
    doc = json.loads(merged.read_text())
    evs = doc["traceEvents"]
    assert {e["pid"] for e in evs if e.get("ph") == "X"} == {0, 1}, (
        "merged timeline must carry span tracks from BOTH hosts"
    )
    pnames = {e["pid"]: e["args"]["name"] for e in evs
              if e.get("name") == "process_name"}
    assert set(pnames) == {0, 1}
    for e in evs:
        if e.get("ph") in ("X", "i"):
            assert isinstance(e.get("ts"), (int, float)) and e["ts"] >= 0
