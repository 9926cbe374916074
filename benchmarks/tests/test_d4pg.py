"""The D4PG configuration under the harness's own check and the limits its
file commits, at a size the CPU holds: the program passes at both compute
dtypes (scan chunk on XLA:CPU and the megakernel, interpreted), and the
check's three faults fail, each by its own number."""

import json
import os

import pytest

from conftest import BENCH

SEED, CHUNK = 7, 8


def small(extra=()):
    """The configuration as committed, limits and all (`check.limits`, set
    from the chip's readings), with the ring cut to what the CPU holds."""
    config = json.load(open(os.path.join(BENCH, "configs", "d4pg-halfcheetah.json")))
    config["flags"] = [f for f in config["flags"] if not f.startswith("--replay_capacity")] + list(extra)
    return config


@pytest.mark.parametrize("extra,kernel", [
    (["--fused_chunk=on"], True),  # the megakernel's categorical branch, interpreted
    (["--fused_chunk=off"], False),  # the scan chunk on XLA:CPU
])
def test_program_passes_the_committed_limits_and_three_faults_fail_them(extra, kernel):
    import synthetic

    config = small(extra)
    for dtype in ("float32", "bfloat16"):
        r = synthetic.run_once(config, SEED, [f"--compute_dtype={dtype}"], chunk=CHUNK)
        assert r["fused_chunk_active"] is kernel
        assert r["ok"], (dtype, r["numbers"])
    control = synthetic.control_once(config, SEED, CHUNK)
    assert not control["ok"]
    assert not control["numbers"]["td0_vs_stated"]["ok"]
    broken = synthetic.run_once(config, SEED, (), chunk=CHUNK, break_step=True)
    assert not broken["ok"] and not broken["numbers"]["change_gap"]["ok"]
    # learning rates 20% under the configuration's: the forward pass is sound,
    # the update is not. At a critic rate of 1e-4 the first updates move the
    # expectation gap so little that `update_effect_gap` has to be loose (the
    # chip's sound readings reach a fifth) and lets this fault pass;
    # `change_gap` holds it: Adam's steps scale with the rate, so every net's
    # change over the chunk is a fifth short, 0.2 against the limit.
    hp = config["reference"]["hp"]
    assert config["check"]["limits"]["change_gap"] < 0.2
    slow = synthetic.run_once(
        config, SEED, [f"--critic_lr={0.8 * hp['critic_lr']}", f"--actor_lr={0.8 * hp['actor_lr']}"], chunk=CHUNK)
    assert not slow["ok"] and slow["numbers"]["td0_vs_stated"]["ok"]
    assert not slow["numbers"]["change_gap"]["ok"]
    assert slow["numbers"]["change_gap"]["value"] == pytest.approx(0.2, abs=0.03)


def test_work_counts_the_51_wide_head():
    from reference import d4pg, ddpg

    env = {"obs_dim": 17, "act_dim": 6}
    hp = {"hidden": [256, 256], "batch_size": 256, "num_atoms": 51}
    d, plain = d4pg.work(env, hp), ddpg.work(env, hp)
    assert d["flops"] - plain["flops"] == 7 * 2 * 256 * 256 * 50  # seven critic passes, 50 more outputs
    assert d["state_bytes"] - plain["state_bytes"] == 2 * 4 * 4 * (256 * 50 + 50)
    assert d["row_bytes"] == plain["row_bytes"]
