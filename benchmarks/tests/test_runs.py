"""The harness end to end where there is no chip, and the check's control.

The rehearsals run `rehearse.py` as a child: it swaps the harness's look for
a chip for its own answer and calls the harness's own `main()` on a copy of
the benchmark to which a cell, a configuration, a traffic mix and a
per-layer metric were added as files only.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TEST_LIMITS = {"init_gap": 0, "td0_vs_stated": 4.0, "update_effect_gap": 0.03, "critic_loss_rel": 1e-2, "change_gap": 0.3}


def last_json(stdout):
    lines = [l for l in stdout.strip().splitlines() if l.strip()]
    try:
        return json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        return None


def child_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def test_without_a_chip_there_is_no_result_and_no_zero_exit():
    cell = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=child_env(), timeout=120, cwd=ROOT,
    )
    assert p.returncode != 0
    assert last_json(p.stdout) is None
    assert "needs 1 TPU chip" in p.stderr


def test_alone_in_a_directory_there_is_no_result_and_no_zero_exit(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    cell = json.load(open(tmp_path / "BENCHMARK.json"))["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=child_env(), timeout=120, cwd=tmp_path,
    )
    assert p.returncode != 0 and last_json(p.stdout) is None


@pytest.fixture(scope="module")
def copy_with_added_files(tmp_path_factory):
    """A copy of the benchmark plus one cell, configuration, mix and metric,
    each a new file; no file of the copy is edited but BENCHMARK.json."""
    root = tmp_path_factory.mktemp("copy")
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({"name": "pendulum-test", "source": "test only", "reduced": [],
                             "file": "benchmarks/configs/pendulum-test.json", "why": "test only"})
    bench["workloads"].append({"name": "pendulum-test.quick", "config": "pendulum-test",
                               "traffic": "quick", "chips": 1, "why": "test only"})
    bench["per_layer"].append({"name": "test.records", "unit": "1", "better": "higher",
                               "source": "program_counter", "layer": "loop",
                               "moves": "grad_steps_per_s", "workloads": ["pendulum-test.quick"]})
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    json.dump({
        "source": "test only",
        "flags": ["--backend=jax_tpu", "--env_id=Pendulum-v1", "--actor_hidden=32,32",
                  "--critic_hidden=32,32", "--replay_capacity=200000"],
        "env": {"id": "Pendulum-v1", "obs_dim": 3, "act_dim": 1, "action_scale": 2.0, "action_offset": 0.0},
        "expects": {"fused_chunk_active": False}, "precision": {"products": "bfloat16"},
        "reference": {"module": "ddpg", "hp": {"hidden": [32, 32], "gamma": 0.99, "tau": 0.001,
                                               "actor_lr": 0.0001, "critic_lr": 0.001, "batch_size": 64}},
        "check": {"control_operands": "float8_e5m2", "limits": TEST_LIMITS},
        "chunk_module": "sample_chunk_fn", "reduced": {},
    }, open(root / "benchmarks/configs/pendulum-test.json", "w"))
    json.dump({"who": "test only", "flags": ["--num_actors=2", "--replay_min_size=500"],
               "warmup": {"warm_chunks": 3, "quiet_s": 0.5, "max_warm_s": 5.0}, "trace_seconds": 0.5},
              open(root / "benchmarks/traffic/quick.json", "w"))
    (root / "benchmarks/metrics/test_records.py").write_text(
        'def read(run):\n    return float(len(run["window"]))\n')
    return root


def rehearse(root, *driver_args, trace=0, seed=3000000019):
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), str(root / "benchmarks"), *driver_args, "--",
         "--workload", "pendulum-test.quick", "--seed", str(seed), "--seconds", "3", "--trace", str(trace)],
        capture_output=True, text=True, env=child_env(), timeout=300, cwd=root,
    )
    lines = p.stdout.strip().splitlines()
    return p, json.loads(lines[-2])["facts"], json.loads(lines[-1])


def test_rehearsal_runs_the_added_cell_to_a_result_line(copy_with_added_files):
    p, facts, result = rehearse(copy_with_added_files)
    assert p.returncode == 0, p.stderr[-2000:]
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"grad_steps_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # the window: opened after warm-up, closed by the first record past 3 s, ended by SIGTERM
    assert 3.0 <= facts["window_s"] < 8.0 and facts["window_records"] >= 2
    assert abs(facts["window_s"] - facts["window_s_by_records"]) < 0.1
    assert "SIGTERM" in p.stderr
    assert facts["leg"] == "scan" and all(facts["invariants"].values())
    assert facts["check"]["updates"] == facts["learner_chunk"]
    for line in ("check init_gap:", "check td0_vs_stated:", "check update_effect_gap:", "check critic_loss_rel:", "check change_gap:"):
        assert line in p.stdout


def test_traced_rehearsal_reports_the_added_metric_and_leaves_out_what_has_nothing_to_read(copy_with_added_files):
    p, facts, result = rehearse(copy_with_added_files, trace=1, seed=5)
    assert p.returncode == 0, p.stderr[-2000:]
    names = set(result["metrics"])
    assert {"test.records", "loop.dispatch_ms", "loop.refresh_ms", "loop.policy_lag_ms",
            "ingest.ms_per_call"} <= names
    assert "actors.env_steps_per_s" not in names  # its `workloads` key lists other cells only
    assert result["metrics"]["test.records"]["value"] == facts["window_records"]
    # a CPU trace has no device plane: the readers of the device trace return nothing
    assert not names & {"chunk.device_ms", "chunk_roofline", "device.idle_pct", "grad_steps_per_s"}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(copy_with_added_files):
    p, facts, result = rehearse(copy_with_added_files, "--break-step", seed=6)
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is False
    assert facts["check"]["numbers"]["change_gap"]["ok"] is False
    assert facts["check"]["numbers"]["td0_vs_stated"]["ok"] is True


def small(name, extra=()):
    config = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    config["flags"] = [f for f in config["flags"] if not f.startswith("--replay_capacity")] + list(extra)
    config["check"]["limits"] = TEST_LIMITS
    return config


@pytest.mark.parametrize("name,extra,kernel", [
    ("ddpg-halfcheetah", ["--fused_chunk=on"], True),  # the megakernel, interpreted
    ("sac-humanoid", [], False),  # the scan chunk on XLA:CPU
])
def test_program_passes_the_check_and_its_control_fails_it(name, extra, kernel):
    import synthetic

    config = small(name, extra)
    for dtype in ("float32", "bfloat16"):  # what the chip computes either way: PERF.md, section 2
        r = synthetic.run_once(config, 7, [f"--compute_dtype={dtype}"], chunk=8)
        assert r["fused_chunk_active"] is kernel
        assert r["ok"], (dtype, r["numbers"])
    control = synthetic.control_once(config, 7, 8)
    assert not control["ok"]
    assert not control["numbers"]["td0_vs_stated"]["ok"]
    broken = synthetic.run_once(config, 7, (), chunk=8, break_step=True)
    assert not broken["ok"] and not broken["numbers"]["change_gap"]["ok"]
    # learning rates 20% under the configuration's: the forward pass is sound, the update is not
    hp = config["reference"]["hp"]
    slow = synthetic.run_once(
        config, 7, [f"--critic_lr={0.8 * hp['critic_lr']}", f"--actor_lr={0.8 * hp['actor_lr']}"], chunk=8)
    assert not slow["ok"] and slow["numbers"]["td0_vs_stated"]["ok"]
    assert 0.1 < slow["numbers"]["update_effect_gap"]["value"] < 0.3
