"""The REDQ configuration under the harness's own check and the limits its
file commits, at a size the CPU holds: the program passes at both compute
dtypes on the scan chunk, the only leg it has; the control fails; learning
rates 20% low fail a limit; a step that hands its state back fails."""

import json
import os

import pytest

from conftest import BENCH

SEED, CHUNK = 7, 8


def small(extra=()):
    """The configuration as committed, limits and all (`check.limits`, set
    from the chip's readings), with the ring cut to what the CPU holds."""
    config = json.load(open(os.path.join(BENCH, "configs", "redq-humanoid.json")))
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
    # a step that hands its state back unchanged reads a change_gap of 1
    broken = synthetic.run_once(config, SEED, (), chunk=CHUNK, break_step=True)
    assert broken["numbers"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)
    assert not broken["numbers"]["change_gap"]["ok"] and not broken["ok"]
    # the minimum over all ten targets where the configuration draws two: at
    # seeded weights the ten lie thousandths apart, and that is ten to a
    # hundred times what bfloat16 products put between two sound forward
    # passes: update 0 fails
    eager = synthetic.run_once(config, SEED, ["--target_subset=10"], chunk=CHUNK)
    assert not eager["ok"] and not eager["numbers"]["td0_vs_stated"]["ok"]


def test_work_is_the_reference_modules():
    from reference import redq, sac

    config = small()
    w = redq.work(config["env"], config["reference"]["hp"])
    assert 2.8e9 < w["flops"] < 3.0e9  # 33.5 critic passes of 85.2 MFLOP and 1.15 actor passes of 87.3
    assert w["row_bytes"] == 4.0 * 256 * 772
    sibling = sac.work(config["env"], json.load(open(os.path.join(BENCH, "configs", "sac-humanoid.json")))["reference"]["hp"])
    assert 1.8 < w["flops"] / sibling["flops"] < 2.0  # ISSUE 33: 1.9 times sac-humanoid's
