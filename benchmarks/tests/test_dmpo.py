"""The DMPO configuration under the harness's own check and the limits its
file commits, at a size the CPU holds (the file's flags with narrower nets and
fewer samples: the harness's check at 512-512-256 on 5,120 rows takes minutes
here): the program passes on the scan chunk, the only leg it has (under
bfloat16 products every limit but the one the synthetic ring's small returns
inflate); the control fails; learning rates 20% low fail a limit; a chunk
that hands its state back reads a change_gap of 1; the five readers this
configuration brought read what the program writes, and nothing where it
writes nothing."""

import importlib
import json
import os

import pytest

from conftest import BENCH

SEED, CHUNK = 7, 8
NARROW = ["--critic_hidden=64,64,32", "--actor_hidden=32,32,32", "--mpo_samples=5"]


def small():
    """The configuration as committed, limits and all (`check.limits`, set
    from the chip's readings), with the ring cut to what the CPU holds and
    the reference told the narrower widths."""
    config = json.load(open(os.path.join(BENCH, "configs", "dmpo-humanoid.json")))
    config["flags"] = [f for f in config["flags"] if not f.startswith("--replay_capacity")] + NARROW
    config["reference"]["hp"].update(critic_hidden=[64, 64, 32], actor_hidden=[32, 32, 32], samples=5)
    return config


def test_program_passes_the_committed_limits_and_the_faults_do_not():
    import synthetic

    config = small()
    sound = synthetic.run_once(config, SEED, ["--compute_dtype=float32"], chunk=CHUNK)
    assert sound["fused_chunk_active"] is False  # supported() says no: the scan leg, by the code's own rule
    assert sound["ok"], sound["numbers"]
    control = synthetic.control_once(config, SEED, CHUNK)
    assert not control["ok"]
    assert not control["numbers"]["td0_vs_stated"]["ok"]
    # bfloat16 products pass every limit but one: on the synthetic ring's
    # returns of size 1 the first three updates move the TD errors by little
    # more than the rounding does, and update_effect_gap reads 0.26 to 0.33
    # at any width (0.0097 at the most on the chip, on the actors' rows, where
    # the limit was set), still twenty times under the control's
    rounded = synthetic.run_once(config, SEED, ["--compute_dtype=bfloat16"], chunk=CHUNK)
    assert [k for k, v in rounded["numbers"].items() if not v["ok"]] in ([], ["update_effect_gap"])
    assert rounded["numbers"]["update_effect_gap"]["value"] < 0.1 * control["numbers"]["update_effect_gap"]["value"]
    # learning rates 20% under the configuration's: the forward pass is
    # sound, the update is not
    hp = config["reference"]["hp"]
    slow = synthetic.run_once(
        config, SEED, [f"--critic_lr={0.8 * hp['critic_lr']}", f"--actor_lr={0.8 * hp['actor_lr']}"], chunk=CHUNK)
    assert not slow["ok"] and slow["numbers"]["td0_vs_stated"]["ok"]
    # a chunk that hands its state back unchanged reads a change_gap of 1
    broken = synthetic.run_once(config, SEED, (), chunk=CHUNK, break_step=True)
    assert broken["numbers"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)
    assert broken["ok"] == (config["check"]["limits"]["change_gap"] >= 1.0)


def test_work_is_the_reference_modules():
    from reference import d4pg, dmpo

    config = json.load(open(os.path.join(BENCH, "configs", "dmpo-humanoid.json")))
    w = dmpo.work(config["env"], config["reference"]["hp"])
    assert 7.6e9 < w["flops"] < 7.7e9  # ISSUE 51: 7.64 GFLOP an update
    assert 0.82 < w["estep_flops"] / w["flops"] < 0.84  # "83% of it the E-step"
    assert w["row_bytes"] == 4.0 * 256 * 772
    # 0.84 M weights with both moments and the targets, read and written
    assert 13.4e6 < w["state_bytes"] / 2 < 13.6e6
    sibling = d4pg.work(
        {"obs_dim": 17, "act_dim": 6},
        json.load(open(os.path.join(BENCH, "configs", "d4pg-halfcheetah.json")))["reference"]["hp"])
    assert 9 < w["flops"] / sibling["flops"] < 11  # against d4pg-halfcheetah's 0.78 GFLOP


def test_the_file_states_the_sources_widths_and_nothing_cut():
    config = json.load(open(os.path.join(BENCH, "configs", "dmpo-humanoid.json")))
    flags = dict(f.lstrip("-").split("=", 1) for f in config["flags"])
    assert flags["actor_hidden"] == "256,256,256" and flags["critic_hidden"] == "512,512,256"
    assert flags["batch_size"] == "256" and flags["num_atoms"] == "51" and flags["n_step"] == "5"
    assert flags["mpo_samples"] == "20" and flags["target_update_period"] == "100"
    assert float(flags["actor_lr"]) == float(flags["critic_lr"]) == 1e-4 and float(flags["dual_lr"]) == 1e-2
    assert [float(flags[k]) for k in ("mpo_epsilon", "mpo_epsilon_penalty", "mpo_epsilon_mean", "mpo_epsilon_stddev")] == [
        0.1, 1e-3, 2.5e-3, 1e-6]
    assert [float(flags[k]) for k in (
        "mpo_init_log_temperature", "mpo_init_log_alpha_mean", "mpo_init_log_alpha_stddev")] == [10.0, 10.0, 1000.0]
    assert (float(flags["v_min"]), float(flags["v_max"])) == (-150.0, 150.0)
    assert set(config["reduced"]) == {"replay_capacity", "num_actors"}
    hp = config["reference"]["hp"]
    assert hp["actor_hidden"] == [256, 256, 256] and hp["critic_hidden"] == [512, 512, 256] and hp["samples"] == 20
    assert config["expects"] == {"fused_chunk_active": False, "chunk_front": "cut"}


def read(metric, run):
    return importlib.import_module("metrics." + metric.replace(".", "_")).read(run)


def test_the_five_readers_read_the_programs_keys_and_nothing_without_them():
    window = [{"learner_steps": 800 * i, "mpo_weight_ess": 18.0 + i, "mpo_kl_mean_ratio": 0.1 * i} for i in (1, 2, 3)]
    assert read("learner.estep_ess", {"window": window}) == pytest.approx(20.0)
    assert read("learner.kl_mean_ratio", {"window": window}) == pytest.approx(0.2)
    for metric in ("learner.estep_ess", "learner.kl_mean_ratio"):
        assert read(metric, {"window": [{"learner_steps": 800}]}) is None
        assert read(metric, {"window": []}) is None
    # no trace, or a program without the scopes (the parent's): nothing, and no raise
    bare = {"trace": None, "summary": {}, "config": {}}
    for metric in ("chunk.estep_pct", "chunk.duals_pct", "chunk.estep_roofline"):
        assert read(metric, bare) is None
    from harness import scopes

    found = {"scopes": {"update/estep": 50.0, "update/estep/lnorm": 5.0, "update/duals": 2.0, "update/duals/optim": 3.0,
                        "update/critic": 20.0, "update/actor": 10.0, "update/optim": 6.0, "update/polyak": 4.0,
                        "gather": 50.0},
             "loop_self": 0.0, "launches": 3}
    assert scopes.ns(found, "update/estep") == 55.0 and scopes.ns(found, "update/duals") == 5.0
    assert scopes.ns(found, "update") == 100.0
    # a reference without `estep_flops` (every sibling's) gives the roofline nothing to read
    from reference import d4pg

    assert "estep_flops" not in d4pg.work({"obs_dim": 17, "act_dim": 6}, {
        "hidden": [400, 300], "batch_size": 256, "num_atoms": 51})
