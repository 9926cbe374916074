"""tools.runs CLI tests (the tier-1 smoke the ISSUE's CI satellite asks
for): summarize + compare over fixture JSONL in the exact schema
metrics.MetricsLogger emits, and the regression gate over two JSON
objects — which must exit nonzero on a synthetic 20% grad-steps/s
regression."""

import json
import subprocess
import sys

import pytest

from distributed_ddpg_tpu.tools import runs


def _write_jsonl(path, records):
    path.write_text("\n".join(json.dumps(r) for r in records) + "\n")


def _fixture_run(path, rate=100.0, dispatch_ms=5.0, p95=9.0):
    """A miniature train run in the real JSONL schema (kind/step/wall_time
    + t_* phase fields + ingest_* fields + eval/final records)."""
    records = []
    for i in range(1, 9):
        records.append({
            "kind": "train", "step": 500 * i, "wall_time": 2.0 * i,
            "learner_steps": 400 * i, "learner_steps_per_sec": rate + i,
            "buffer_fill": 500 * i, "episode_return": -900.0 + 10 * i,
            "critic_loss": 0.5, "mean_q": 1.0 + i,
            "t_dispatch_ms": dispatch_ms, "n_dispatch": 50,
            "t_dispatch_p50": dispatch_ms * 0.9,
            "t_dispatch_p95": p95, "t_dispatch_max": p95 * 2,
            "t_ingest_ms": 0.4, "n_ingest": 50,
            "ingest_rows_per_sec": 8000.0, "ingest_ship_calls": 4,
            "ingest_coalesce_mean": 2.0, "ingest_stall_ms": 0.0,
            "ingest_queue_rows": 128,
        })
        if i % 4 == 0:
            records.append({
                "kind": "eval", "step": 500 * i, "wall_time": 2.0 * i + 0.5,
                "eval_return": -800.0 + 50 * i,
            })
    records.append({
        "kind": "final", "step": 4000, "wall_time": 17.0,
        "learner_steps": 3200, "learner_steps_per_sec": rate,
        "final_return": -600.0,
    })
    _write_jsonl(path, records)
    return records


def test_summarize_digest_and_render(tmp_path):
    path = tmp_path / "run.jsonl"
    _fixture_run(path)
    digest = runs.summarize_run(str(path))
    assert digest["records"] == {"train": 8, "eval": 2, "final": 1}
    assert digest["steps"] == {"first": 500, "last": 4000}
    assert digest["metrics"]["learner_steps_per_sec"]["last"] == 108.0
    assert digest["phases"]["dispatch"]["p95_ms"] == 9.0
    assert digest["phases"]["dispatch"]["calls"] == 400
    assert digest["ingest"]["ingest_rows_per_sec"]["steady"] == 8000.0
    assert digest["eval"]["best"] == -400.0
    assert digest["final"]["final_return"] == -600.0
    text = runs.render_summary(digest)
    assert "dispatch" in text and "ingest_rows_per_sec" in text

    # Interleaved non-JSON lines (echo streams mix prints into stdout
    # captures) must be skipped, not fatal.
    noisy = tmp_path / "noisy.jsonl"
    noisy.write_text(
        "resumed from ckpt at step 3\n"
        + path.read_text()
        + "{broken json\n"
    )
    assert runs.summarize_run(str(noisy))["records"]["train"] == 8


def test_summarize_nstep_rows_and_edge_mass(tmp_path):
    """A categorical n-step run's records: the cumulative `nstep_*` pair
    (metrics.nstep_counters) gets its own section with the short share, and
    `c51_edge_mass` sits among the headline metrics; a run without the
    keys (every other family) gets neither."""
    path = tmp_path / "d4pg.jsonl"
    records = _fixture_run(path)
    for i, r in enumerate(r for r in records if r["kind"] == "train"):
        r.update(nstep_rows=1000 * (i + 1), nstep_short_rows=4 * (i + 1),
                 c51_edge_mass=0.002 * (i + 1))
    _write_jsonl(path, records)
    digest = runs.summarize_run(str(path))
    assert digest["nstep"]["nstep_rows"]["last"] == 8000
    assert digest["nstep"]["nstep_short_rows"]["last"] == 32
    assert digest["nstep"]["nstep_short_share"]["last"] == pytest.approx(0.004)
    assert digest["metrics"]["c51_edge_mass"]["last"] == pytest.approx(0.016)
    text = runs.render_summary(digest)
    assert "n-step rows" in text and "nstep_short_share" in text
    assert "c51_edge_mass" in text

    plain = tmp_path / "plain.jsonl"
    _fixture_run(plain)
    digest = runs.summarize_run(str(plain))
    assert digest["nstep"] == {} and "c51_edge_mass" not in digest["metrics"]
    assert "n-step rows" not in runs.render_summary(digest)


def test_summarize_recovery_counters(tmp_path, capsys):
    """Fault history (docs/RESILIENCE.md): the cumulative recovery
    counters train.py logs must surface in the digest and the rendered
    summary, so `tools.runs summarize` shows a run's fault history."""
    path = tmp_path / "run.jsonl"
    records = _fixture_run(path)
    for i, r in enumerate(records):
        if r["kind"] in ("train", "final"):
            r["actor_respawns"] = min(i, 3)
            r["actor_quarantined"] = 0
            r["ckpt_write_retries"] = 1
            r["emergency_ckpt"] = 0
    _write_jsonl(path, records)
    digest = runs.summarize_run(str(path))
    assert digest["recovery"]["actor_respawns"]["last"] == 3
    assert digest["recovery"]["ckpt_write_retries"]["last"] == 1
    rendered = runs.render_summary(digest)
    assert "recovery / fault history" in rendered
    assert "actor_respawns" in rendered
    # A clean run renders the all-zero note instead of a table.
    clean = tmp_path / "clean.jsonl"
    recs2 = _fixture_run(clean)
    for r in recs2:
        if r["kind"] in ("train", "final"):
            r.update(actor_respawns=0, actor_quarantined=0,
                     ckpt_write_retries=0, emergency_ckpt=0)
    _write_jsonl(clean, recs2)
    assert "clean run" in runs.render_summary(runs.summarize_run(str(clean)))
    # compare: recovery counters ride the A/B table, lower-is-better.
    text, rows = runs.compare_runs(str(clean), str(path))
    row = [r for r in rows if r[0] == "actor_respawns"]
    assert row and row[0][2] == 3


def test_summarize_cli_smoke(tmp_path, capsys):
    path = tmp_path / "run.jsonl"
    _fixture_run(path)
    assert runs.main(["summarize", str(path)]) == 0
    out = capsys.readouterr().out
    assert "phase breakdown" in out
    assert runs.main(["summarize", "--json", str(path)]) == 0
    digest = json.loads(capsys.readouterr().out)
    assert digest["phases"]["dispatch"]["p95_ms"] == 9.0


def test_compare_flags_regressions(tmp_path, capsys):
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _fixture_run(a, rate=100.0, dispatch_ms=5.0, p95=9.0)
    _fixture_run(b, rate=70.0, dispatch_ms=8.0, p95=30.0)  # slower + fatter tail
    assert runs.main(["compare", str(a), str(b)]) == 0
    out = capsys.readouterr().out
    rate_line = next(l for l in out.splitlines()
                     if l.startswith("learner_steps_per_sec"))
    assert "!" in rate_line  # >=5% worse, higher-is-better
    p95_line = next(l for l in out.splitlines()
                    if l.startswith("t_dispatch_p95"))
    assert "!" in p95_line   # fatter tail flagged (lower-is-better)


# --------------------------------------------------------------------------
# gate: two JSON objects (e.g. two result lines of benchmarks/run.py)
# compared by dotted keys; exit nonzero on a synthetic 20% regression
# --------------------------------------------------------------------------

_RATE = "metrics.grad_steps_per_s.value"
_SETUP = "metrics.setup_s.value"


def _result_line(path, rate, setup_s=60.0):
    """One object in the shape of a benchmarks/run.py result line."""
    path.write_text(json.dumps({
        "correct": True, "attempted": 1, "failed": 0,
        "metrics": {
            "grad_steps_per_s": {"value": rate, "unit": "steps/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        },
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }))


def _gate(tmp_path, *extra):
    return runs.main([
        "gate", str(tmp_path / "base.json"), str(tmp_path / "cand.json"),
        *extra,
    ])


def test_gate_passes_within_threshold(tmp_path):
    _result_line(tmp_path / "base.json", 100.0)
    _result_line(tmp_path / "cand.json", 95.0)  # -5% < 10% threshold
    assert _gate(tmp_path, "--keys", _RATE) == 0
    # No default key: the comparer knows nothing of what it is handed.
    with pytest.raises(SystemExit):
        _gate(tmp_path)


def test_gate_fails_on_20pct_grad_steps_regression(tmp_path, capsys):
    """A synthetic 20% grad-steps/s regression must exit nonzero at the
    default 10% threshold."""
    _result_line(tmp_path / "base.json", 100.0)
    _result_line(tmp_path / "cand.json", 80.0)
    assert _gate(tmp_path, "--keys", _RATE) == 2
    out = capsys.readouterr().out
    assert f"FAIL {_RATE}" in out and "GATE FAIL" in out


def test_gate_lower_is_better_and_dotted_keys(tmp_path):
    _result_line(tmp_path / "base.json", 100.0, setup_s=60.0)
    _result_line(tmp_path / "cand.json", 100.0, setup_s=90.0)
    # set-up +50%: fails only when gated lower-is-better; the dotted
    # rate key beside it resolves into the nested object and holds.
    assert _gate(tmp_path, "--keys", f"{_RATE},-{_SETUP}") == 2
    assert _gate(tmp_path, "--keys", f"{_RATE},{_SETUP}") == 0


def test_gate_missing_candidate_key_fails(tmp_path):
    """A metric that vanished from the candidate must FAIL (a silently
    dropped field reading as healthy is how regressions hide)."""
    _result_line(tmp_path / "base.json", 100.0)
    (tmp_path / "cand.json").write_text(json.dumps({"metrics": {}}))
    assert _gate(tmp_path, "--keys", _RATE) == 2


@pytest.mark.parametrize(
    "key, base, good, bad, skip_base",
    [
        # higher-is-better rate (device actors' rows/s)
        ("devactor_rows_per_s", 5e5, 5.2e5, 2e5, {}),
        # lower-is-better bytes landed per ingested row (sharded replay)
        ("-replay_ingest_bytes_per_row", 172.0, 171.0, 400.0, {}),
        # two lower-is-better latency tails (serving, network front)
        ("-serve_p95_ms", 5.0, 5.2, 9.0, {}),
        ("-front_wire_p95_ms", 5.0, 5.2, 9.0, {}),
        # A zero baseline on a lower-is-better integer COUNTER is a pin:
        # any nonzero candidate regressed from "never happened", which no
        # relative threshold can express. A FLOAT 0.0 baseline is a tail
        # that saw no samples and must keep SKIPping, not fail the first
        # candidate that records any.
        ("-guardrail_rollbacks", 0, 0, 2, {"guardrail_rollbacks": 0.0}),
    ],
)
def test_gate_key_semantics(key, base, good, bad, skip_base):
    """SKIP when the baseline has nothing to hold the key to; FAIL past
    the threshold or when the candidate drops the key; pass inside it."""
    name = key.lstrip("-")
    ok, lines = runs.gate_objects(skip_base, {name: bad}, 0.1, (key,))
    assert ok and lines[0].startswith(f"SKIP {name}")
    ok, lines = runs.gate_objects({name: base}, {name: bad}, 0.1, (key,))
    assert not ok and lines[0].startswith(f"FAIL {name}")
    ok, lines = runs.gate_objects({name: base}, {}, 0.1, (key,))
    assert not ok and lines[0].startswith(f"FAIL {name}: missing")
    ok, lines = runs.gate_objects({name: base}, {name: good}, 0.1, (key,))
    assert ok and lines[0].startswith("ok")


def test_module_entrypoint_runs_without_jax_import(tmp_path):
    """`python -m distributed_ddpg_tpu.tools.runs` is the documented CLI;
    it must work as a module AND must not initialize jax (instant start,
    CI-safe on accelerator-less boxes) — asserted by poisoning the jax
    import path."""
    path = tmp_path / "run.jsonl"
    _fixture_run(path)
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.modules['jax'] = None\n"
         "from distributed_ddpg_tpu.tools.runs import main\n"
         f"sys.exit(main(['summarize', {str(path)!r}]))"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "phase breakdown" in proc.stdout
