"""DrQ-v2 against its plain reference (benchmarks/reference/drqv2.py), at a
small size on the CPU (28x28 frames, 8 channels, feature_dim 16, hidden 32,
batch 8): the seeded states equal to the last bit; the program's sampling
chunk, as `train()` launches it, follows the reference's updates on the same
ring rows; references bent on purpose each fail a stated number that the
sound one passes; the harness's own comparison (`check.compare`) reads inside
limits; `work()` counts what the issue counts.

The reference is loaded from its one file under benchmarks/, by path, so
there is no second copy to drift.
"""

import importlib
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.learner import LAST_UPDATE_KEYS, PIXEL_KEYS, init_train_state, metric_keys
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.types import ObsSpec, packed_width

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")

SIDE, ACT = 28, 5
OBS = ObsSpec((9, SIDE, SIDE), "uint8")
ENV = {"id": "PixelHumanoidStandIn-v0", "obs_shape": list(OBS.shape), "obs_dtype": "uint8", "act_dim": ACT,
       "action_scale": 1.0, "action_offset": 0.0}
# The source's rates are 8e-5 and its tau 0.01: three such updates move
# nothing a float32 comparison could tell from rounding. Rates of 3e-3 make
# every bend below visible in three updates.
HP = {
    "channels": 8, "feature_dim": 16, "hidden": [32, 32], "gamma": 0.99, "tau": 0.01, "actor_lr": 3e-3,
    "critic_lr": 3e-3, "batch_size": 8, "aug_pad": 4, "noise_clip": 0.3, "sigma_schedule": [1.0, 0.1, 2000],
    "frames_per_update": 4,
}
# What a bend may change without a new trace of the reference: handed to the
# jitted follow as arrays.
TRACED = ("tau", "actor_lr", "critic_lr", "noise_clip", "aug_pad")
UPDATES, SEED, ROWS = 3, 11, 48


@pytest.fixture(scope="module")
def drqv2():
    sys.path.insert(0, BENCH)
    try:
        return importlib.import_module("reference.drqv2")
    finally:
        sys.path.remove(BENCH)


def config(**kw):
    base = dict(
        backend="jax_tpu", env_id=ENV["id"], pixels=True, twin_critic=True, action_insert_layer=0,
        actor_backend="device", num_actors=0, device_actor_envs=4, device_actor_chunk=1, n_step=3,
        actor_hidden=tuple(HP["hidden"]), critic_hidden=tuple(HP["hidden"]), encoder_channels=HP["channels"],
        feature_dim=HP["feature_dim"], batch_size=HP["batch_size"], actor_lr=HP["actor_lr"],
        critic_lr=HP["critic_lr"], tau=HP["tau"], target_noise_clip=HP["noise_clip"], aug_pad=HP["aug_pad"],
        explore_sigma_schedule="1.0,0.1,2000", replay_capacity=256, seed=SEED, scale_batch_with_data=False,
    )
    base.update(kw)
    return DDPGConfig(**base)


def rows(seed, n, discount=HP["gamma"] ** 3):
    """Packed pixel rows: smooth images (a convolution reads something of
    them) whose next image is the first shifted and dimmed, actions in the
    box, rewards of size 1, a few terminal rows."""
    rng = np.random.default_rng(seed)
    yy, xx = np.meshgrid(np.arange(SIDE), np.arange(SIDE), indexing="ij")
    phase = rng.uniform(0, 6.28, (n, 9, 1, 1))
    freq = rng.uniform(0.1, 0.6, (n, 9, 1, 1))
    wave = 127.5 + 100.0 * np.sin(freq * yy + phase) * np.cos(freq * xx - phase) + rng.normal(0, 8, (n, 9, SIDE, SIDE))
    obs = np.clip(wave, 0, 255).astype(np.uint8)
    nobs = np.clip(0.9 * np.roll(wave, 1, axis=-1) + rng.normal(0, 8, wave.shape), 0, 255).astype(np.uint8)
    fields = np.concatenate(
        [rng.uniform(-1, 1, (n, ACT)), rng.normal(size=(n, 1)), discount * (rng.uniform(size=(n, 1)) > 0.05)], axis=1
    ).astype(np.float32)
    return jnp.asarray(np.concatenate(
        [obs.reshape(n, -1).view(np.float32), fields, nobs.reshape(n, -1).view(np.float32), np.ones((n, 1), np.float32)],
        axis=1))


def view(state):
    return {"actor": state.actor_params, "critic": state.critic_params, "target_critic": state.target_critic_params}


class Ring:
    """What `run_sample_chunk` needs of a DeviceReplay."""

    def __init__(self, storage):
        self.storage, self.size = storage, jnp.asarray(storage.shape[0], jnp.int32)
        self.dispatch_lock = threading.RLock()

    def device_state(self):
        return self.storage, self.size


@pytest.fixture(scope="module")
def storage():
    made = rows(3, ROWS)
    assert made.shape == (ROWS, packed_width(OBS, ACT))
    return made


@pytest.fixture(scope="module")
def chunk(drqv2, storage):
    """The program's own K updates through ShardedLearner's sampling chunk on
    one device: (seeded state, the reference's seeded state, state after,
    td [K, B], the chunk's metrics, the rows drawn [K, B, width], the key)."""
    learner = ShardedLearner(
        config(), OBS, ACT, ENV["action_scale"], ENV["action_offset"], chunk_size=UPDATES,
        mesh=mesh_lib.make_mesh(devices=jax.devices()[:1]),
    )
    assert not learner.fused_chunk_active and learner.chunk_front == "xla" and learner.obs_dim == OBS.words
    s0 = jax.tree.map(jnp.copy, learner.state)
    key0 = jnp.copy(learner._key)
    out = learner.run_sample_chunk(Ring(storage))
    _, idx = drqv2.c.draw_indices(key0, UPDATES, HP["batch_size"], storage.shape[0])
    return s0, drqv2.init(SEED, ENV, HP), out.state, out.td_errors, out.metrics, storage[idx], key0


@pytest.fixture(scope="module")
def follow(drqv2):
    """The reference's K updates from `ref0` on `batches` with the TRACED
    settings as arrays: one trace serves the sound run and every bend that
    is a number."""
    def run(ref0, batches, dyn):
        return jax.lax.scan(drqv2.make_step(SEED, ENV, {**HP, **dyn}), ref0, batches)

    jitted = jax.jit(run)

    def call(ref0, batches, **changed):
        return jitted(ref0, batches, {k: jnp.asarray({**HP, **changed}[k]) for k in TRACED})

    return call


def gaps(s0, s1, td, metrics, ref0, ref1, ref):
    """The numbers the comparison is made on, as {name: (value, tolerance)}.
    Both sides are float32 on the CPU: what is left between a sound program
    and the reference is the order of rounding."""
    out = {
        "td0": (float(jnp.max(jnp.abs(td[0] - ref["td"][0]))), 2e-5),
        "td": (float(jnp.max(jnp.abs(td - ref["td"]))), 5e-4),
        "critic_loss": (abs(float(metrics["critic_loss"]) / float(jnp.mean(ref["critic_loss"])) - 1.0), 1e-3),
        "actor_loss": (abs(float(metrics["actor_loss"]) - float(jnp.mean(ref["actor_loss"]))), 1e-4),
        "explore_sigma": (abs(float(metrics["explore_sigma"]) - float(ref["explore_sigma"][-1])), 1e-6),
        "aug_offset_mean": (abs(float(metrics["aug_offset_mean"]) - float(jnp.mean(ref["aug_offset_mean"]))), 1e-5),
        "encoder_grad_norm": (
            abs(float(metrics["encoder_grad_norm"]) / float(jnp.mean(ref["encoder_grad_norm"])) - 1.0), 1e-3),
    }
    after, before = view(s1), view(s0)
    for k in after:
        d_ref = [np.asarray(b1 - b0) for b1, b0 in zip(jax.tree.leaves(ref1[k]), jax.tree.leaves(ref0[k]))]
        d_prog = [np.asarray(a1 - a0) for a1, a0 in zip(jax.tree.leaves(after[k]), jax.tree.leaves(before[k]))]
        floor = np.median([np.linalg.norm(d) for d in d_ref])
        out["change." + k] = (
            max(float(np.linalg.norm(dp - dr) / max(np.linalg.norm(dr), floor, 1e-30)) for dr, dp in zip(d_ref, d_prog)),
            0.01,
        )
    return out


def test_seeded_states_are_equal_to_the_last_bit(drqv2, chunk):
    s0, ref0 = chunk[0], chunk[1]
    fresh = init_train_state(config(), OBS, ACT, SEED)
    assert s0.target_actor_params is None and "target_actor" not in ref0
    for k, tree in view(s0).items():
        assert jax.tree.structure(tree) == jax.tree.structure(ref0[k]) == jax.tree.structure(view(fresh)[k])
        for a, b, c in zip(jax.tree.leaves(tree), jax.tree.leaves(ref0[k]), jax.tree.leaves(view(fresh)[k])):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)
    w = np.asarray(s0.critic_params["encoder"][1]["w"]).reshape(HP["channels"], -1)
    np.testing.assert_allclose(w @ w.T, 2.0 * np.eye(HP["channels"]), atol=1e-5)  # orthogonal rows, gain sqrt(2)
    t = np.asarray(s0.actor_params["trunk"]["w"])
    np.testing.assert_allclose(t.T @ t, np.eye(HP["feature_dim"]), atol=1e-5)
    # another seed, another state; seeds past 2**31 are seeds like any other
    other = drqv2.init(2**31 + 5, ENV, HP)
    assert not np.array_equal(other["critic"]["trunk"]["w"], ref0["critic"]["trunk"]["w"])


def test_program_chunk_follows_the_reference(chunk, follow):
    s0, ref0, s1, td, metrics, batches, _ = chunk
    ref1, ref = follow(ref0, batches)
    assert set(metrics) == set(metric_keys(config())) and set(PIXEL_KEYS) <= set(metrics)
    assert "explore_sigma" in LAST_UPDATE_KEYS and "aug_offset_mean" not in LAST_UPDATE_KEYS
    for name, (value, tol) in gaps(s0, s1, td, metrics, ref0, ref1, ref).items():
        assert value <= tol, (name, value, tol)
    assert int(s1.step) == UPDATES == int(ref1["step"])
    assert 0.0 <= float(metrics["aug_offset_mean"]) <= 8.0 and float(metrics["explore_sigma"]) < 1.0
    assert float(jnp.max(jnp.abs(td))) > 0.1  # rows that say something
    # the encoder has no target, and the target trails the online trunk and heads
    assert set(s1.target_critic_params) == {"trunk", "heads"}
    assert not np.array_equal(s1.target_critic_params["trunk"]["w"], s1.critic_params["trunk"]["w"])


def test_the_harness_comparison_reads_inside_limits(drqv2, chunk, storage):
    """`check.compare` and `reference_side`, as benchmarks/run.py calls them,
    on the chunk above: float32 on both sides reads far under any limit a
    chip's readings would set; a state handed back unchanged reads 1."""
    sys.path.insert(0, BENCH)
    try:
        from harness import check
    finally:
        sys.path.remove(BENCH)
    s0, _, s1, td, metrics, _, key0 = chunk
    drawn = (drqv2, SEED, ENV, HP, key0, storage, jnp.asarray(ROWS, jnp.int32), UPDATES, HP["batch_size"])
    prog0, prog1 = check.program_view(s0), check.program_view(s1)
    ref = check.reference_side(drawn, "bfloat16")
    numbers, shown = check.compare(prog0, prog1, ref[0], ref[1], td, {k: float(v) for k, v in metrics.items()}, *ref[2:])
    assert numbers["init_gap"] == 0.0
    assert numbers["td0_vs_stated"] < 0.05 and numbers["update_effect_gap"] < 1e-3
    assert numbers["critic_loss_rel"] < 1e-3 and numbers["change_gap"] < 1e-2, numbers
    stuck, _ = check.compare(prog0, prog0, ref[0], ref[1], td, {k: float(v) for k, v in metrics.items()}, *ref[2:])
    assert stuck["change_gap"] == pytest.approx(1.0, abs=1e-3)


# What each fault moves, by a stated number: the sound program reads under the
# tolerance, the bent reference over ten times it. A bend is a changed number
# (`follow`'s one trace), changed rows, or a patched function of the reference.
def one_offset_for_the_batch(drqv2):
    draw = drqv2.draw_offsets
    return "draw_offsets", lambda key, batch, pad: jnp.broadcast_to(draw(key, 1, pad), (batch, 4))


def actor_loss_moves_the_encoder(drqv2):
    return "features_for_actor", lambda f: f


def target_through_a_target_encoder(drqv2):
    stale = drqv2.init(SEED, ENV, HP)["critic"]["encoder"]  # where a target encoder at tau 0.01 would still be
    return "encoder_for_targets", lambda s: stale


BENT = {
    "augmentation_left_out": (dict(aug_pad=0), None, "td0"),
    "one_offset_for_the_whole_batch": ({}, one_offset_for_the_batch, "td0"),
    "actor_loss_moves_the_encoder": ({}, actor_loss_moves_the_encoder, "change.critic"),
    "target_through_a_target_encoder": ({}, target_through_a_target_encoder, "td"),
    "sigma_unclipped": (dict(noise_clip=1e9), None, "td0"),
    "gamma_cubed_as_gamma": ("rows", None, "td0"),
    "tau_0.005": (dict(tau=0.005), None, "change.target_critic"),
    "rates_20pct_low": (dict(actor_lr=0.8 * HP["actor_lr"], critic_lr=0.8 * HP["critic_lr"]), None, "change.critic"),
}


@pytest.mark.parametrize("bend", sorted(BENT))
def test_a_bent_reference_fails_a_stated_number(drqv2, chunk, follow, monkeypatch, bend):
    s0, ref0, s1, td, metrics, batches, _ = chunk
    changed, patch, number = BENT[bend]
    run = follow
    if patch is not None:
        monkeypatch.setattr(drqv2, *patch(drqv2))
        run = lambda ref0, b: jax.jit(  # a patched function: a trace of its own
            lambda s, b: jax.lax.scan(drqv2.make_step(SEED, ENV, HP), s, b))(ref0, b)
    if changed == "rows":
        # rows whose discount is gamma where the actors folded gamma ** 3
        col = OBS.words + ACT + 1
        batches = batches.at[..., col].multiply(HP["gamma"] ** -2)
        changed = {}
    bent = gaps(s0, s1, td, metrics, ref0, *run(ref0, batches, **changed))
    assert bent[number][0] > 10 * bent[number][1], (bend, bent)


def test_work_counts_what_the_issue_counts(drqv2):
    env = {"obs_shape": [9, 84, 84], "act_dim": 21}
    hp = {**HP, "channels": 32, "feature_dim": 100, "hidden": [1024, 1024], "batch_size": 256}
    w = drqv2.work(env, hp)
    macs = 1681 * 32 * 81 + (1521 + 1369 + 1225) * 32 * 288
    assert drqv2.encoder_macs(env, hp) == (macs, 1681 * 32 * 81) and 84e6 < 2 * macs < 85e6  # "84.5 MFLOP an image"
    assert w["encoder_flops"] == 2.0 * 256 * (4 * macs - 1681 * 32 * 81) and 83e9 < w["encoder_flops"] < 87e9
    assert 100e9 < w["flops"] < 115e9  # ISSUE 47: "about 108 GFLOP"
    assert w["row_bytes"] == 256 * 127104.0
    assert 150e6 < w["state_bytes"] / 2 < 170e6  # "162 MB" of state, read and written
    trunks = 2.0 * 256 * 7 * 39200 * 100
    assert 13e9 < trunks < 15e9 and w["flops"] - w["encoder_flops"] - trunks < 10e9  # heads and policy: "about 8"
