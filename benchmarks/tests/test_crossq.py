"""The CrossQ configuration under the harness's own check and the limits its
file commits, at a size the CPU holds: the program passes at both compute
dtypes on the scan chunk, the only leg it has; the control fails; learning
rates 20% low and another beta_1 fail a limit; what the limits cannot hold
(a state handed back) is said; the two readers this configuration brought read what the program writes, and nothing
where it writes nothing."""

import importlib
import json
import os

import pytest

from conftest import BENCH

SEED, CHUNK = 7, 8


def small(extra=()):
    """The configuration as committed, limits and all (`check.limits`, set
    from the chip's readings), with the ring cut to what the CPU holds."""
    config = json.load(open(os.path.join(BENCH, "configs", "crossq-humanoid.json")))
    config["flags"] = [f for f in config["flags"] if not f.startswith("--replay_capacity")] + list(extra)
    return config


def test_program_passes_the_committed_limits_and_the_faults_do_not():
    import synthetic

    config = small()
    for dtype in ("float32", "bfloat16"):
        r = synthetic.run_once(config, SEED, [f"--compute_dtype={dtype}"], chunk=CHUNK)
        assert r["fused_chunk_active"] is False  # supported() says no: the scan leg, by the code's own rule
        assert r["ok"], (dtype, r["numbers"])
    control = synthetic.control_once(config, SEED, CHUNK)
    assert not control["ok"]
    assert not control["numbers"]["td0_vs_stated"]["ok"]
    # learning rates 20% under the configuration's: the forward pass is
    # sound, the update is not, and `update_effect_gap` holds it
    hp = config["reference"]["hp"]
    slow = synthetic.run_once(
        config, SEED, [f"--critic_lr={0.8 * hp['critic_lr']}", f"--actor_lr={0.8 * hp['actor_lr']}"], chunk=CHUNK)
    assert not slow["ok"] and slow["numbers"]["td0_vs_stated"]["ok"]
    assert not slow["numbers"]["update_effect_gap"]["ok"]
    # What the committed limits do NOT hold in this cell, as in td3-halfcheetah's
    # (PERF.md sec. 7, 15 and 21): two sound trajectories part inside the chip's
    # 800-update chunk, `change_gap` cannot tell them from the control, and its
    # limit lies above the 1 that a chunk which hands its state back unchanged
    # reads. What holds that fault: in a run the invariant "actor parameters
    # moved" (run.py; test_runs.py's break-step rehearsal), in tier-1
    # tests/test_reference_crossq.py.
    broken = synthetic.run_once(config, SEED, (), chunk=CHUNK, break_step=True)
    assert broken["numbers"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3) and broken["ok"]
    # Adam at the default beta_1 where the configuration has the paper's 0.5
    heavy = synthetic.run_once(config, SEED, ["--adam_b1=0.9"], chunk=CHUNK)
    assert not heavy["ok"] and heavy["numbers"]["td0_vs_stated"]["ok"]


def test_work_is_the_reference_modules():
    from reference import crossq, sac

    config = small()
    w = crossq.work(config["env"], config["reference"]["hp"])
    assert 32.0e9 < w["flops"] < 32.8e9  # ISSUE 38: "~32 GFLOP"
    assert w["row_bytes"] == 4.0 * 256 * 772
    assert 120e6 < w["state_bytes"] / 2 < 125e6  # weights and both moments: "120 MB in float32"
    sibling = sac.work(config["env"], json.load(open(os.path.join(BENCH, "configs", "sac-humanoid.json")))["reference"]["hp"])
    assert 20 < w["flops"] / sibling["flops"] < 22  # twenty times sac-humanoid's 1.56 GFLOP


def read(metric, run):
    return importlib.import_module("metrics." + metric.replace(".", "_")).read(run)


def test_the_two_readers_read_the_programs_keys_and_nothing_without_them():
    window = [{"learner_steps": 800 * i, "bn_stat_gap": 0.1 * i} for i in (1, 2, 3)]
    assert read("critic.norm_stat_gap", {"window": window}) == pytest.approx(0.2)
    assert read("critic.norm_stat_gap", {"window": [{"learner_steps": 800}]}) is None
    assert read("critic.norm_stat_gap", {"window": []}) is None
    # no trace, or a program without the scope: nothing, and no raise
    assert read("chunk.norm_pct", {"trace": None, "summary": {}, "config": {}}) is None
    from harness import scopes

    found = {"scopes": {"update/critic": 60.0, "update/critic/norm": 25.0, "update/actor/norm": 5.0,
                        "update/optim": 10.0, "gather": 50.0}, "loop_self": 0.0, "launches": 3}
    assert scopes.ns(found, "update/critic/norm", "update/actor/norm") == 30.0
    assert scopes.ns(found, "update") == 100.0 and scopes.ns(found, "update/critic") == 85.0
