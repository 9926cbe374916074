"""The DrQ-v2 configuration under the harness's own check and the limits its
file commits, at a size the CPU holds (the file's flags with 28x28 frames,
8 channels, feature_dim 16, hidden 32, batch 8: the check at 84x84 and 1,024
takes minutes here): the program passes on the scan chunk, the only leg it
has; the control fails; learning rates 20% low fail a limit; a state handed
back reads 1; the file states the source's widths and cuts none; the three
readers this configuration brought read what the program writes, and nothing
where it writes nothing. synthetic.py's ring and learner take flat float
observations, so the pixel ring and the learner's build are this file's."""

import importlib
import json
import os
import sys
import threading

import pytest

from conftest import BENCH

SEED, CHUNK, SIDE, ROWS = 7, 4, 28, 200
NARROW = ["--critic_hidden=32,32", "--actor_hidden=32,32", "--encoder_channels=8", "--feature_dim=16", "--batch_size=8"]


def small():
    """The configuration as committed, limits and all (`check.limits`, set
    from the chip's readings), with the ring cut to what the CPU holds and
    the reference told the narrower widths and the smaller frames."""
    config = json.load(open(os.path.join(BENCH, "configs", "drqv2-humanoid.json")))
    keep = lambda f: not f.startswith(("--replay_capacity", "--batch_size", "--learner_chunk"))
    config["flags"] = [f for f in config["flags"] if keep(f)] + NARROW + ["--replay_capacity=256"]
    config["reference"]["hp"].update(channels=8, feature_dim=16, hidden=[32, 32], batch_size=8)
    config["env"].update(obs_shape=[9, SIDE, SIDE], obs_dim=9 * SIDE * SIDE)
    return config


class PixelRing:
    """What `run_sample_chunk` needs of a DeviceReplay: seeded rows in the
    pixel layout, smooth images, a few terminal rows."""

    def __init__(self, seed, rows, env):
        import jax.numpy as jnp
        import numpy as np

        rng = np.random.default_rng(seed ^ 0x51D)
        c, h, w = env["obs_shape"]
        yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
        f, p = rng.uniform(0.1, 0.6, (rows, c, 1, 1)), rng.uniform(0, 6.28, (rows, c, 1, 1))
        wave = 127.5 + 100 * np.sin(f * yy + p) * np.cos(f * xx - p) + rng.normal(0, 8, (rows, c, h, w))
        obs = np.clip(wave, 0, 255).astype(np.uint8).reshape(rows, -1)
        nobs = np.clip(0.9 * np.roll(wave, 1, -1) + rng.normal(0, 8, wave.shape), 0, 255).astype(np.uint8).reshape(rows, -1)
        a = env["act_dim"]
        fields = np.concatenate(
            [rng.uniform(-1, 1, (rows, a)), rng.normal(size=(rows, 1)), 0.97 * (rng.uniform(size=(rows, 1)) > 0.02)], 1
        ).astype(np.float32)
        self.storage = jnp.asarray(np.concatenate(
            [obs.view(np.float32), fields, nobs.view(np.float32), np.ones((rows, 1), np.float32)], 1))
        self.size = jnp.asarray(rows, jnp.int32)
        self.dispatch_lock = threading.RLock()

    def device_state(self):
        return self.storage, self.size


def run_once(config, seed, extra_flags=(), break_step=False, control=False):
    """synthetic.run_once for byte observations: one first-chunk check of a
    learner built as the trainer builds it; with `control`, the control's
    verdict on the same rows beside it."""
    import jax

    import synthetic
    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.types import ObsSpec
    from harness.check import ChunkCheck

    env = config["env"]
    cfg = DDPGConfig.from_flags(
        list(config["flags"]) + ["--actor_backend=device", "--num_actors=0", "--device_actor_envs=4", f"--seed={seed}"]
        + list(extra_flags))
    learner = ShardedLearner(
        cfg, ObsSpec(tuple(env["obs_shape"]), env["obs_dtype"]), env["act_dim"], env["action_scale"],
        env["action_offset"], chunk_size=CHUNK, mesh=mesh_lib.make_mesh(1, 1, jax.devices()[:1]),
    )
    if break_step:
        synthetic.break_learner(learner)
    reference = importlib.import_module("reference." + config["reference"]["module"])
    args = (reference, seed, env, config["reference"]["hp"], config["check"]["limits"], config["precision"]["products"])
    check = synthetic.control_check(config["check"]["control_operands"], *args) if control else ChunkCheck(*args)
    check.install(ShardedLearner)
    try:
        learner.run_sample_chunk(PixelRing(seed, ROWS, env))
    finally:
        check.uninstall()
    return dict(check.result, fused_chunk_active=bool(learner.fused_chunk_active))


def test_program_passes_the_committed_limits_and_the_faults_do_not():
    config = small()
    r = run_once(config, SEED, control=True)
    assert r["fused_chunk_active"] is False  # supported() says no: the scan leg, by the code's own rule
    assert r["ok"], r["numbers"]
    assert not r["control"]["ok"] and not r["control"]["numbers"]["td0_vs_stated"]["ok"]
    # learning rates 20% under the configuration's: the forward pass is
    # sound, the update is not, and `update_effect_gap` holds it
    hp = config["reference"]["hp"]
    slow = run_once(config, SEED, [f"--critic_lr={0.8 * hp['critic_lr']}", f"--actor_lr={0.8 * hp['actor_lr']}"])
    assert not slow["ok"] and slow["numbers"]["td0_vs_stated"]["ok"]
    assert not slow["numbers"]["update_effect_gap"]["ok"]
    # a chunk that hands its state back unchanged reads a change_gap of 1
    broken = run_once(config, SEED, break_step=True)
    assert broken["numbers"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)
    assert broken["ok"] == (config["check"]["limits"]["change_gap"] >= 1.0)


def test_work_is_the_reference_modules():
    from reference import drqv2, simba

    config = json.load(open(os.path.join(BENCH, "configs", "drqv2-humanoid.json")))
    w = drqv2.work(config["env"], config["reference"]["hp"])
    assert 100e9 < w["flops"] < 115e9  # ISSUE 47: "about 108 GFLOP"
    assert 83e9 < w["encoder_flops"] < 87e9  # "about 86"
    assert w["row_bytes"] == 256 * 127104.0
    assert 150e6 < w["state_bytes"] / 2 < 170e6  # ISSUE 47's 162 MB, read and written
    sibling = json.load(open(os.path.join(BENCH, "configs", "simba-humanoid.json")))
    assert 3 < w["flops"] / simba.work(sibling["env"], sibling["reference"]["hp"])["flops"] < 5


def test_the_file_states_the_sources_widths_and_nothing_cut():
    config = json.load(open(os.path.join(BENCH, "configs", "drqv2-humanoid.json")))
    flags = dict(f.lstrip("-").split("=", 1) for f in config["flags"])
    assert flags["critic_hidden"] == flags["actor_hidden"] == "1024,1024" and flags["batch_size"] == "256"
    assert flags["encoder_channels"] == "32" and flags["feature_dim"] == "100" and flags["aug_pad"] == "4"
    assert float(flags["actor_lr"]) == float(flags["critic_lr"]) == 8e-5 and float(flags["tau"]) == 0.01
    assert flags["n_step"] == "3" and flags["target_noise_clip"] == "0.3"
    assert flags["explore_sigma_schedule"] == "1.0,0.1,2000000" and flags["replay_capacity"] == "65536"
    assert config["env"]["obs_shape"] == [9, 84, 84] and config["env"]["obs_dtype"] == "uint8" and config["env"]["act_dim"] == 21
    assert set(config["reduced"]) == {"replay_capacity"}
    for key in ("flat_transition_row", "sigma_from_learner_step", "initialisers", "stand_in"):
        assert key in config["assumed"]  # the four departures
    hp = config["reference"]["hp"]
    assert hp["channels"] == 32 and hp["feature_dim"] == 100 and hp["hidden"] == [1024, 1024]
    assert hp["sigma_schedule"] == [1.0, 0.1, 2000000] and hp["noise_clip"] == 0.3 and hp["aug_pad"] == 4
    assert config["expects"] == {"fused_chunk_active": False, "chunk_front": "xla"}
    traffic = json.load(open(os.path.join(BENCH, "traffic", "devpixels.json")))
    mix = dict(f.lstrip("-").split("=", 1) for f in traffic["flags"])
    assert mix == {"actor_backend": "device", "num_actors": "0", "device_actor_envs": "64", "device_actor_chunk": "1",
                   "max_ingest_ratio": "2", "replay_min_size": "65536", "warmup_uniform_steps": "65536"}
    assert int(flags["learner_chunk"]) * 2 == int(mix["device_actor_envs"])  # 2 rows an update


def read(metric, run):
    return importlib.import_module("metrics." + metric.replace(".", "_")).read(run)


def test_the_three_readers_read_the_programs_scopes_and_nothing_without_them(monkeypatch):
    from harness import scopes
    from reference import drqv2, simba

    config = json.load(open(os.path.join(BENCH, "configs", "drqv2-humanoid.json")))
    found = {"scopes": {"update/encoder": 50.0e6, "update/augment": 20.0e6, "prep/pixels": 10.0e6, "update/critic": 60.0e6,
                        "update/optim": 30.0e6, "gather": 30.0e6}, "loop_self": 0.0, "launches": 3}
    run = {"trace": {"x": 1}, "summary": {"learner_chunk": 32}, "config": config, "reference": drqv2,
           "peaks": {"flops_per_s": 197e12}}
    monkeypatch.setattr(scopes, "of_run", lambda r: found if r.get("trace") else None)
    assert read("chunk.encoder_pct", run) == pytest.approx(100 * 50 / 160)
    assert read("chunk.augment_pct", run) == pytest.approx(100 * 30 / 200)
    least_ms = 32 * drqv2.work(config["env"], config["reference"]["hp"])["encoder_flops"] / 197e12 * 1e3
    assert read("chunk.encoder_roofline", run) == pytest.approx(100 * least_ms / 50.0)
    assert 20 < read("chunk.encoder_roofline", run) < 35  # 13.7 ms of products in 50 ms
    # no trace, a program without the scopes, or a reference without the count: nothing, and no raise
    for metric in ("chunk.encoder_pct", "chunk.encoder_roofline", "chunk.augment_pct"):
        assert read(metric, {**run, "trace": None}) is None
    found["scopes"] = {"update/critic": 60.0e6, "gather": 30.0e6}
    for metric in ("chunk.encoder_pct", "chunk.encoder_roofline", "chunk.augment_pct"):
        assert read(metric, run) is None
    found["scopes"]["update/encoder"] = 50.0e6
    sibling = json.load(open(os.path.join(BENCH, "configs", "simba-humanoid.json")))
    assert read("chunk.encoder_roofline", {**run, "reference": simba, "config": sibling}) is None
