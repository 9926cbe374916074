"""The TD3 configuration under the harness's own check and the limits its
file commits, at a size the CPU holds: the program passes at both compute
dtypes (scan chunk on XLA:CPU and the megakernel's twin branch, interpreted),
the control fails, and two faults are shown for what the committed limits do
with them."""

import json
import os

import pytest

from conftest import BENCH

SEED, CHUNK = 7, 8


def small(extra=()):
    """The configuration as committed, limits and all (`check.limits`, set
    from the chip's readings), with the ring cut to what the CPU holds."""
    config = json.load(open(os.path.join(BENCH, "configs", "td3-halfcheetah.json")))
    config["flags"] = [f for f in config["flags"] if not f.startswith("--replay_capacity")] + list(extra)
    return config


@pytest.mark.parametrize("extra,kernel", [
    (["--fused_chunk=on"], True),  # the megakernel's twin branch, interpreted
    (["--fused_chunk=off"], False),  # the scan chunk on XLA:CPU
])
def test_program_passes_the_committed_limits_and_what_they_do_with_four_faults(extra, kernel):
    import synthetic

    config = small(extra)
    for dtype in ("float32", "bfloat16"):
        r = synthetic.run_once(config, SEED, [f"--compute_dtype={dtype}"], chunk=CHUNK)
        assert r["fused_chunk_active"] is kernel
        assert r["ok"], (dtype, r["numbers"])
    control = synthetic.control_once(config, SEED, CHUNK)
    assert not control["ok"]
    assert not control["numbers"]["td0_vs_stated"]["ok"]
    assert not control["numbers"]["update_effect_gap"]["ok"]  # on the chip, over 800 updates, critic_loss_rel too
    # `change_gap` does not hold this cell's two state faults. On the chip a
    # sound run's actor travels up to 2.2 times as far as the reference's over
    # 800 free-running updates at a rate of 1e-3 (64 seeds: median gap 0.046,
    # ten over 0.2, one 1.23), so the committed limit is three times that and
    # lies above what the faults read:
    limit = config["check"]["limits"]["change_gap"]
    assert limit > 3.0
    # a step that hands its state back unchanged (with the chunk's own TD
    # errors) reads exactly 1. What holds it: in a run the invariant "actor
    # parameters moved" (run.py; test_runs.py's break-step rehearsal), in
    # tier-1 tests/test_reference_td3.py.
    broken = synthetic.run_once(config, SEED, (), chunk=CHUNK, break_step=True)
    assert broken["numbers"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3) and broken["ok"]
    # The delay ignored: an actor and targets that move on every update where
    # the configuration says every second. Update 0 moves them on both sides
    # (step 0), so the forward pass and the first updates' TD errors agree
    # (the actor enters a TD error only through its target, tau at a time);
    # over eight updates the actor makes twice the Adam steps and every
    # target twice the Polyak steps, and `change_gap` reads 2.7: under the
    # limit too, and `update_effect_gap` 0.005. Neither holds it; tier-1 does
    # (tests/test_reference_td3.py follows every net's change over a chunk
    # that starts on an odd step to a hundredth, and a skipped update's actor
    # and targets to the bit).
    eager = synthetic.run_once(config, SEED, ["--policy_delay=1"], chunk=CHUNK)
    assert eager["numbers"]["td0_vs_stated"]["ok"] and eager["numbers"]["update_effect_gap"]["ok"]
    assert 2.0 < eager["numbers"]["change_gap"]["value"] < limit
    # learning rates 20% under the configuration's: the forward pass is
    # sound, the update is not, and `update_effect_gap` holds it
    hp = config["reference"]["hp"]
    slow = synthetic.run_once(
        config, SEED, [f"--critic_lr={0.8 * hp['critic_lr']}", f"--actor_lr={0.8 * hp['actor_lr']}"], chunk=CHUNK)
    assert not slow["ok"] and slow["numbers"]["td0_vs_stated"]["ok"]
    assert not slow["numbers"]["update_effect_gap"]["ok"]


def test_work_is_the_reference_modules():
    from reference import td3

    config = small()
    w = td3.work(config["env"], config["reference"]["hp"])
    assert 0.30e9 < w["flops"] < 0.32e9  # 2.5 actor passes and 9.5 critic passes of ~25.8 MFLOP
    assert w["row_bytes"] == 4.0 * 100 * 43
