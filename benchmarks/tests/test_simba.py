"""The SimBa configuration under the harness's own check and the limits its
file commits, at a size the CPU holds (the file's flags with narrower nets:
the harness's check at 512 <-> 2,048 takes minutes here): the program passes
at both compute dtypes on the scan chunk, the only leg it has; the control
fails; learning rates 20% low fail a limit; what the limits cannot hold (the
decay left out, a state handed back) is said, with what holds it instead;
the three readers this configuration brought read what the program writes,
and nothing where it writes nothing."""

import importlib
import json
import os

import pytest

from conftest import BENCH

SEED, CHUNK = 7, 8
NARROW = ["--critic_hidden=64,64", "--actor_hidden=32"]


def small():
    """The configuration as committed, limits and all (`check.limits`, set
    from the chip's readings), with the ring cut to what the CPU holds and
    the reference told the narrower widths."""
    config = json.load(open(os.path.join(BENCH, "configs", "simba-humanoid.json")))
    config["flags"] = [f for f in config["flags"] if not f.startswith("--replay_capacity")] + NARROW
    config["reference"]["hp"].update(critic_hidden=[64, 64], actor_hidden=[32])
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
    # What the committed limits do NOT hold: the decay left out. At the
    # source's rate 1e-4 and decay 1e-2 an update shrinks a weight by 1e-6 of
    # itself, against Adam's step of 1e-4: a ten-thousandth of the chunk's
    # change, far under what bfloat16 products leave between program and
    # reference. It reads here, at float32, as a change_gap a thousand times
    # the sound run's and still under the limit; tier-1 holds the decay
    # (tests/test_reference_simba.py: `weight_decay_0`, tests/test_simba.py).
    sound = synthetic.run_once(config, SEED, ["--compute_dtype=float32"], chunk=CHUNK)
    bare = synthetic.run_once(config, SEED, ["--compute_dtype=float32", "--weight_decay=0"], chunk=CHUNK)
    assert bare["numbers"]["change_gap"]["value"] > 100 * sound["numbers"]["change_gap"]["value"]
    assert bare["ok"]
    # a chunk that hands its state back unchanged reads a change_gap of 1
    broken = synthetic.run_once(config, SEED, (), chunk=CHUNK, break_step=True)
    assert broken["numbers"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)
    assert broken["ok"] == (config["check"]["limits"]["change_gap"] >= 1.0)


def test_work_is_the_reference_modules():
    from reference import sac, simba

    config = json.load(open(os.path.join(BENCH, "configs", "simba-humanoid.json")))
    w = simba.work(config["env"], config["reference"]["hp"])
    assert 26e9 < w["flops"] < 32e9  # ISSUE 44: "about 27 GFLOP"
    assert w["row_bytes"] == 4.0 * 256 * 772
    # weights with both moments and the targets: ISSUE 44's 143 MB, read and written
    assert 140e6 < w["state_bytes"] / 2 < 146e6
    sibling = sac.work(config["env"], json.load(open(os.path.join(BENCH, "configs", "sac-humanoid.json")))["reference"]["hp"])
    assert 17 < w["flops"] / sibling["flops"] < 21  # against sac-humanoid's 1.56 GFLOP


def test_the_file_states_the_sources_widths_and_nothing_cut():
    config = json.load(open(os.path.join(BENCH, "configs", "simba-humanoid.json")))
    flags = dict(f.lstrip("-").split("=", 1) for f in config["flags"])
    assert flags["critic_hidden"] == "512,512" and flags["actor_hidden"] == "128" and flags["batch_size"] == "256"
    assert float(flags["actor_lr"]) == float(flags["critic_lr"]) == 1e-4
    assert float(flags["weight_decay"]) == 1e-2 and float(flags["tau"]) == 0.005
    assert set(config["reduced"]) == {"replay_capacity", "num_actors"}
    assert next(iter(config["assumed"])) == "rsnorm_feed"  # the statistics' feed first
    hp = config["reference"]["hp"]
    assert hp["critic_hidden"] == [512, 512] and hp["actor_hidden"] == [128] and hp["weight_decay"] == 1e-2
    assert config["expects"] == {"fused_chunk_active": False, "chunk_front": "cut"}


def read(metric, run):
    return importlib.import_module("metrics." + metric.replace(".", "_")).read(run)


def test_the_three_readers_read_the_programs_keys_and_nothing_without_them():
    window = [{"learner_steps": 800 * i, "resid_share": 0.1 * i, "policy_forward_us": 100.0 * i} for i in (1, 2, 3)]
    window.append({"learner_steps": 3200, "resid_share": 0.2})  # an interval without a forward
    assert read("critic.resid_share", {"window": window}) == pytest.approx(0.2)
    assert read("actors.policy_forward_us", {"window": window}) == pytest.approx(200.0)
    for metric in ("critic.resid_share", "actors.policy_forward_us"):
        assert read(metric, {"window": [{"learner_steps": 800}]}) is None
        assert read(metric, {"window": []}) is None
    # no trace, or a program without the scopes: nothing, and no raise
    assert read("chunk.blocknorm_pct", {"trace": None, "summary": {}, "config": {}}) is None
    from harness import scopes
    from metrics import chunk_blocknorm_pct

    found = {"scopes": {"update/critic": 60.0, "update/critic/lnorm": 20.0, "update/critic/rsnorm": 2.0,
                        "update/actor/lnorm": 7.0, "update/actor/rsnorm": 1.0, "update/optim": 10.0, "gather": 50.0},
             "loop_self": 0.0, "launches": 3}
    assert scopes.ns(found, *chunk_blocknorm_pct.SCOPES) == 30.0
    assert scopes.ns(found, "update") == 100.0
