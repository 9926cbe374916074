"""The PQL configuration under the harness's own check and the limits its
file commits, at a size the CPU holds (nets 32-16-8, batch 64): the program
passes at both compute dtypes on the scan chunk, the control fails, learning
rates 20% low fail `update_effect_gap`; the three readers the cell brings read
what they say, and nothing where there is nothing to read."""

import json
import os

import pytest

from conftest import BENCH

SEED, CHUNK = 7, 8


def small(extra=()):
    """The configuration as committed, limits and all (`check.limits`, set
    from the chip's readings), with nets, batch and ring cut to what the CPU
    holds."""
    config = json.load(open(os.path.join(BENCH, "configs", "pql-isaac-humanoid.json")))
    cut = ("--replay_capacity", "--actor_hidden", "--critic_hidden", "--batch_size", "--learner_chunk")
    # the configuration's flags parse beside its mix's alone (the stand-in has no host worker)
    traffic = json.load(open(os.path.join(BENCH, "traffic", "devactors.json")))
    config["flags"] = [f for f in config["flags"] if not f.startswith(cut)] + [
        "--actor_hidden=32,16,8", "--critic_hidden=32,16,8", "--batch_size=64",
        *(f for f in traffic["flags"] if not f.startswith(("--replay_min_size", "--warmup_uniform_steps"))), *extra]
    config["reference"]["hp"].update(hidden=[32, 16, 8], batch_size=64)
    return config


def test_program_passes_the_committed_limits_the_control_and_slow_rates_do_not():
    import synthetic

    config = small()
    for dtype in ("float32", "bfloat16"):
        r = synthetic.run_once(config, SEED, [f"--compute_dtype={dtype}"], chunk=CHUNK)
        assert r["fused_chunk_active"] is False
        assert r["ok"], (dtype, r["numbers"])
    control = synthetic.control_once(config, SEED, CHUNK)
    assert not control["ok"]
    assert not control["numbers"]["td0_vs_stated"]["ok"]
    assert not control["numbers"]["update_effect_gap"]["ok"]  # on the chip, over 96 updates, critic_loss_rel too
    # a step that hands its state back unchanged reads 1 where the limit is under it
    broken = synthetic.run_once(config, SEED, (), chunk=CHUNK, break_step=True)
    assert broken["numbers"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)
    assert broken["ok"] == (config["check"]["limits"]["change_gap"] >= 1.0)
    # learning rates 20% under the configuration's: the forward pass is
    # sound, the update is not, and `update_effect_gap` holds it
    hp = config["reference"]["hp"]
    slow = synthetic.run_once(
        config, SEED, [f"--critic_lr={0.8 * hp['critic_lr']}", f"--actor_lr={0.8 * hp['actor_lr']}"], chunk=CHUNK)
    assert not slow["ok"] and slow["numbers"]["td0_vs_stated"]["ok"]
    assert not slow["numbers"]["update_effect_gap"]["ok"]


def test_the_files_say_what_the_issue_says():
    config = json.load(open(os.path.join(BENCH, "configs", "pql-isaac-humanoid.json")))
    traffic = json.load(open(os.path.join(BENCH, "traffic", "devactors.json")))
    bench = json.load(open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "pql-isaac-humanoid")
    assert entry["reduced"] == [] == list(config["reduced"])  # nothing cut
    assert next(iter(config["assumed"])) == "dynamics"  # the stand-in first
    flags = dict(f.lstrip("-").split("=", 1) for f in config["flags"] + traffic["flags"])
    assert (flags["actor_hidden"], flags["critic_hidden"]) == ("512,256,128", "512,256,128")
    assert (flags["batch_size"], flags["n_step"], flags["replay_capacity"]) == ("8192", "3", "5000000")
    assert (flags["device_actor_envs"], flags["num_actors"], flags["policy_delay"]) == ("4096", "0", "2")
    assert (flags["explore_sigma_min"], flags["explore_sigma_max"]) == ("0.05", "0.8")
    # one rollout step per 8 updates: 4,096 rows a step over 512 rows an update
    assert int(flags["device_actor_envs"]) / float(flags["max_ingest_ratio"]) == 8
    assert int(flags["learner_chunk"]) == 8 * int(flags["device_actor_chunk"])
    cell = next(w for w in bench["workloads"] if w["name"] == "pql-isaac-humanoid.devactors")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("pql-isaac-humanoid", "devactors", 1)
    mine = [m["name"] for m in bench["per_layer"] if m.get("workloads") == ["pql-isaac-humanoid.devactors"]]
    assert mine == ["actors.device_rows_per_s", "actors.device_share_pct", "replay.fill_pct"]


def test_the_cells_readers_read_their_records_and_launches():
    from harness import records
    from metrics import actors_device_rows_per_s, actors_device_share_pct, replay_fill_pct

    window = [{"devactor_rows_per_s": 700000.0}, {"devactor_rows_per_s": 800000.0}, {"learner_steps": 5}]
    run = {
        "window": window, "open": {"buffer_fill": 2_500_000}, "records": records,
        "config": {"flags": ["--replay_capacity=5000000"]},
        "summary": {"setup_spans": {"setup_build": 1.0}},
        "trace": {"busy_s": 2.0, "launches": {
            "jit_devactor_rollout": {"count": 3, "median_s": 0.01, "total_s": 0.03},
            "jit_ring_insert": {"count": 3, "median_s": 0.02, "total_s": 0.07},
            "jit_sample_chunk_fn": {"count": 3, "median_s": 0.6, "total_s": 1.9}}},
    }
    assert actors_device_rows_per_s.read(run) == 750000.0
    assert replay_fill_pct.read(run) == 50.0
    assert actors_device_share_pct.read(run) == pytest.approx(100.0 * 0.10 / 2.0)
    # a program with no device pool (the parent, any `free` cell): nothing to read, nothing raised
    plain = dict(run, window=[{"learner_steps": 5}], open={}, trace=dict(run["trace"], launches={
        "jit_ring_insert": {"count": 3, "median_s": 0.02, "total_s": 0.07}}))
    assert actors_device_rows_per_s.read(plain) is None
    assert actors_device_share_pct.read(plain) is None
    assert replay_fill_pct.read(plain) is None
    assert actors_device_share_pct.read(dict(run, trace=None)) is None


def test_work_is_the_reference_modules():
    from reference import pql

    config = json.load(open(os.path.join(BENCH, "configs", "pql-isaac-humanoid.json")))
    w = pql.work(config["env"], config["reference"]["hp"])
    assert 39.5e9 < w["flops"] < 40.5e9  # 31.6 GFLOP every update and half of the policy's 16.6
    assert w["row_bytes"] == 4.0 * 8192 * 240
