"""Recurrent TD3 against its plain reference (benchmarks/reference/rtd3.py),
at a small size on the CPU (windows of 8 steps, 16 units, batch 4): the seeded
states equal to the last bit; the program's sampling chunk, as `train()`
launches it, follows the reference's updates on the same ring rows, per-step
TD errors, both losses and the state after three updates; references bent on
purpose each fail a stated number that the sound one passes; the harness's own
comparison (`check.compare`) reads inside limits on [K, B, L] TD errors;
`work()` counts what the issue counts.

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
from distributed_ddpg_tpu.learner import LAST_UPDATE_KEYS, RECURRENT_KEYS, init_train_state, metric_keys
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.types import ObsSpec, packed_width

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")

OBS_DIM, ACT, STEPS = 6, 3, 8
OBS = ObsSpec((OBS_DIM,), steps=STEPS)
ENV = {"id": "OccludedHumanoidStandIn-v0", "obs_dim": OBS_DIM, "act_dim": ACT, "action_scale": 1.0, "action_offset": 0.0}
# The source's rates are 3e-4 and its tau 0.005: three such updates move
# nothing a float32 comparison could tell from rounding. Rates of 3e-3 and a
# tau of 0.05 make every bend below visible in three updates.
HP = {
    "seq_len": STEPS, "rnn_hidden": 16, "obs_embed": 8, "action_embed": 4, "reward_embed": 4, "hidden": [16, 16],
    "gamma": 0.99, "tau": 0.05, "actor_lr": 3e-3, "critic_lr": 3e-3, "batch_size": 4, "target_noise": 0.2,
    "target_noise_clip": 0.5,
}
UPDATES, SEED, ROWS = 3, 11, 32


@pytest.fixture(scope="module")
def rtd3():
    sys.path.insert(0, BENCH)
    try:
        return importlib.import_module("reference.rtd3")
    finally:
        sys.path.remove(BENCH)


def config(**kw):
    base = dict(
        backend="jax_tpu", env_id=ENV["id"], recurrent=True, twin_critic=True, action_insert_layer=0,
        actor_backend="device", num_actors=0, device_actor_envs=4, device_actor_chunk=1, exploration="gaussian",
        explore_sigma_min=0.1, explore_sigma_max=0.1, seq_len=STEPS, rnn_hidden=HP["rnn_hidden"],
        obs_embed=HP["obs_embed"], action_embed=HP["action_embed"], reward_embed=HP["reward_embed"],
        actor_hidden=tuple(HP["hidden"]), critic_hidden=tuple(HP["hidden"]), batch_size=HP["batch_size"],
        actor_lr=HP["actor_lr"], critic_lr=HP["critic_lr"], tau=HP["tau"], target_noise=HP["target_noise"],
        target_noise_clip=HP["target_noise_clip"], replay_capacity=256, seed=SEED, scale_batch_with_data=False,
    )
    base.update(kw)
    return DDPGConfig(**base)


def rows(seed, n):
    """Packed window rows: smooth observations, actions in the box, rewards of
    size 1; a third of the rows are young episodes (a real prefix, zeros
    behind it) and some steps terminate, the last real one of a few rows
    among them."""
    rng = np.random.default_rng(seed)
    t = np.arange(STEPS + 1)[None, :, None]
    obs = np.sin(rng.uniform(0.2, 1.0, (n, 1, OBS_DIM)) * t + rng.uniform(0, 6.28, (n, 1, OBS_DIM)))
    action = rng.uniform(-1, 1, (n, STEPS, ACT))
    reward = rng.normal(size=(n, STEPS))
    count = np.where(rng.uniform(size=n) < 0.35, rng.integers(1, STEPS, n), STEPS)
    real = (np.arange(STEPS)[None, :] < count[:, None]).astype(np.float32)
    seen = (np.arange(STEPS + 1)[None, :] <= count[:, None]).astype(np.float32)
    term = np.zeros((n, STEPS))
    term[np.arange(n), count - 1] = rng.uniform(size=n) < 0.4  # an episode's end is its last real step
    return jnp.asarray(np.concatenate([
        (obs * seen[..., None]).reshape(n, -1), (action * real[..., None]).reshape(n, -1),
        reward * real, term * real, real,
    ], axis=1).astype(np.float32))


def view(state):
    return {"actor": state.actor_params, "critic": state.critic_params,
            "target_actor": state.target_actor_params, "target_critic": state.target_critic_params}


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
    assert made.shape == (ROWS, packed_width(OBS, ACT)) == (ROWS, (STEPS + 1) * OBS_DIM + STEPS * (ACT + 3))
    return made


def lively(net):
    """`net` with its heads' last layers 300 times their seeded size: seeded,
    a final layer is U(+-3e-3), Q and the policy's action read a thousandth
    of a reward, and no fault behind a head (a target, a memory, the noise)
    moves a TD error by more than rounding. The same on both sides."""
    def last(chain_):
        return (*chain_[:-1], jax.tree.map(lambda x: 300.0 * x, chain_[-1]))

    key = "head" if "head" in net else "heads"
    return {**net, key: last(net[key])}


def run_chunk(rtd3, storage, lively_heads):
    """The program's own K updates through ShardedLearner's sampling chunk on
    one device, from the seeded state or from it made `lively` (the
    reference's too): (state before, the reference's, state after, td [K, B,
    L], the chunk's metrics, the rows drawn [K, B, width], the key)."""
    heads = lively if lively_heads else (lambda net: net)
    learner = ShardedLearner(
        config(), OBS, ACT, ENV["action_scale"], ENV["action_offset"], chunk_size=UPDATES,
        mesh=mesh_lib.make_mesh(devices=jax.devices()[:1]),
    )
    assert not learner.fused_chunk_active and learner.chunk_front == "xla" and learner.obs.steps == STEPS
    seeded = learner.state
    learner.state = jax.device_put(seeded._replace(
        actor_params=heads(seeded.actor_params), critic_params=heads(seeded.critic_params),
        target_actor_params=heads(seeded.target_actor_params),
        target_critic_params=heads(seeded.target_critic_params),
    ), learner._state_sharding)
    s0 = jax.tree.map(jnp.copy, learner.state)
    key0 = jnp.copy(learner._key)
    out = learner.run_sample_chunk(Ring(storage))
    _, idx = rtd3.c.draw_indices(key0, UPDATES, HP["batch_size"], storage.shape[0])
    ref0 = rtd3.init(SEED, ENV, HP)
    ref0 = {**ref0, **{k: heads(ref0[k]) for k in view(s0)}}
    return s0, ref0, out.state, out.td_errors, out.metrics, storage[idx], key0


@pytest.fixture(scope="module")
def chunk(rtd3, storage):
    return run_chunk(rtd3, storage, lively_heads=True)


def follow(rtd3, ref0, batches, **changed):
    return jax.jit(lambda s, b: jax.lax.scan(rtd3.make_step(SEED, ENV, {**HP, **changed}), s, b))(ref0, batches)


def gaps(s0, s1, td, metrics, ref0, ref1, ref):
    """The numbers the comparison is made on, as {name: (value, tolerance)}.
    Both sides are float32 on the CPU: what is left between a sound program
    and the reference is the order of rounding."""
    out = {
        "td0": (float(jnp.max(jnp.abs(td[0] - ref["td"][0]))), 1e-5),
        "td": (float(jnp.max(jnp.abs(td - ref["td"]))), 1e-4),
        "critic_loss": (abs(float(metrics["critic_loss"]) / float(jnp.mean(ref["critic_loss"])) - 1.0), 1e-3),
        "actor_loss": (abs(float(metrics["actor_loss"]) - float(jnp.mean(ref["actor_loss"]))), 1e-4),
        "seq_valid_frac": (abs(float(metrics["seq_valid_frac"]) - float(jnp.mean(ref["seq_valid_frac"]))), 1e-6),
    }
    after, before = view(s1), view(s0)
    for k in after:
        d_ref = [np.asarray(b1 - b0) for b1, b0 in zip(jax.tree.leaves(ref1[k]), jax.tree.leaves(ref0[k]))]
        d_prog = [np.asarray(a1 - a0) for a1, a0 in zip(jax.tree.leaves(after[k]), jax.tree.leaves(before[k]))]
        floor = np.median([np.linalg.norm(d) for d in d_ref])
        out["change." + k] = (
            max(float(np.linalg.norm(dp - dr) / max(np.linalg.norm(dr), floor, 1e-30)) for dr, dp in zip(d_ref, d_prog)),
            2e-3,  # the sound program reads 3e-6 and less
        )
    return out


def test_seeded_states_are_equal_to_the_last_bit(rtd3):
    s0, ref0 = init_train_state(config(), OBS, ACT, SEED), rtd3.init(SEED, ENV, HP)
    for k, tree in view(s0).items():
        assert jax.tree.structure(tree) == jax.tree.structure(ref0[k])
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(ref0[k])):
            np.testing.assert_array_equal(a, b)
    x = HP["obs_embed"] + HP["action_embed"] + HP["reward_embed"]
    assert s0.actor_params["lstm"]["w"].shape == (x + HP["rnn_hidden"], 4 * HP["rnn_hidden"])
    assert s0.critic_params["heads"][0]["w"].shape == (2, HP["rnn_hidden"] + HP["obs_embed"], HP["hidden"][0])
    assert s0.critic_params["shortcut"]["w"].shape == (OBS_DIM + ACT, HP["obs_embed"])
    # another seed, another state; seeds past 2**31 are seeds like any other
    other = rtd3.init(2**31 + 5, ENV, HP)
    assert not np.array_equal(other["critic"]["lstm"]["w"], ref0["critic"]["lstm"]["w"])


def test_program_chunk_follows_the_reference(rtd3, chunk):
    s0, ref0, s1, td, metrics, batches, _ = chunk
    ref1, ref = follow(rtd3, ref0, batches)
    assert td.shape == (UPDATES, HP["batch_size"], STEPS) == ref["td"].shape  # a TD error a step
    assert set(metrics) == set(metric_keys(config())) and set(RECURRENT_KEYS) <= set(metrics)
    assert "seq_valid_frac" not in LAST_UPDATE_KEYS and "td3_twin_gap" in LAST_UPDATE_KEYS
    for name, (value, tol) in gaps(s0, s1, td, metrics, ref0, ref1, ref).items():
        assert value <= tol, (name, value, tol)
    assert float(metrics["td3_twin_gap"]) == pytest.approx(float(ref["twin_gap"][-1]), rel=1e-4)
    assert int(s1.step) == UPDATES == int(ref1["step"])
    assert 0.5 < float(metrics["seq_valid_frac"]) < 1.0  # some rows are young episodes
    assert float(jnp.max(jnp.abs(td))) > 0.1  # rows that say something
    mask = rtd3.unpack(batches, ENV, HP)["mask"]
    assert float(jnp.max(jnp.abs(td * (1.0 - mask)))) == 0.0  # a padded step reads 0
    # every target leaf trails its online leaf, the memories' among them
    for net in ("actor", "critic"):
        moved, target = view(s1)[net]["lstm"]["w"], view(s1)["target_" + net]["lstm"]["w"]
        assert not np.array_equal(target, moved) and not np.array_equal(target, view(s0)["target_" + net]["lstm"]["w"])


def test_the_harness_comparison_reads_inside_limits(rtd3, storage):
    """`check.compare` and `reference_side`, as benchmarks/run.py calls them,
    on the chunk above and its [K, B, L] TD errors: float32 on both sides reads
    far under any limit a chip's readings would set; a state handed back
    unchanged reads 1."""
    sys.path.insert(0, BENCH)
    try:
        from harness import check
    finally:
        sys.path.remove(BENCH)
    s0, _, s1, td, metrics, _, key0 = run_chunk(rtd3, storage, lively_heads=False)  # the harness seeds its own
    drawn = (rtd3, SEED, ENV, HP, key0, storage, jnp.asarray(ROWS, jnp.int32), UPDATES, HP["batch_size"])
    prog0, prog1 = check.program_view(s0), check.program_view(s1)
    ref = check.reference_side(drawn, "bfloat16")
    numbers, shown = check.compare(prog0, prog1, ref[0], ref[1], td, {k: float(v) for k, v in metrics.items()}, *ref[2:])
    assert numbers["init_gap"] == 0.0
    assert numbers["td0_vs_stated"] < 0.05 and numbers["update_effect_gap"] < 1e-3
    assert numbers["critic_loss_rel"] < 1e-3 and numbers["change_gap"] < 1e-2, numbers
    stuck, _ = check.compare(prog0, prog0, ref[0], ref[1], td, {k: float(v) for k, v in metrics.items()}, *ref[2:])
    assert stuck["change_gap"] == pytest.approx(1.0, abs=1e-3)
    # the control's rounding, the next precision under bfloat16, reads far over the stated one's
    control = check.follow(*drawn, operand_dtype="float8_e5m2", updates=1)[2]["td"][0]
    assert float(jnp.linalg.norm(control - ref[2]["td"][0])) > 4.0 * float(jnp.linalg.norm(ref[3] - ref[2]["td"][0]))


# What each fault moves, by a stated number: the sound program reads under the
# tolerance, the bent reference over ten times it. A bend is a patched function
# of the reference (each of the equations' choices is one).
BENT = {
    "the_mask_ignored": ("mask_of", lambda b: jnp.ones_like(b["mask"]), "critic_loss"),
    "last_step_bootstrapped_through_d": ("bootstrap", lambda b: jnp.ones_like(b["terminated"]), "td0"),
    "smoothing_noise_left_out": ("smoothing_noise", lambda key, k, hp, shape: jnp.zeros(shape), "td0"),
    "critic_memory_fed_the_policys_action": (
        "actions_the_critic_remembers", lambda ring, pi: pi, "change.actor"),
    "target_memories_left_out_of_polyak": (
        "leaves_that_trail",
        lambda online, target, tau: {
            **jax.tree.map(lambda o, t: tau * o + (1 - tau) * t, online, target), "lstm": target["lstm"]},
        "change.target_critic"),
}


@pytest.mark.parametrize("bend", sorted(BENT))
def test_a_bent_reference_fails_a_stated_number(rtd3, chunk, monkeypatch, bend):
    s0, ref0, s1, td, metrics, batches, _ = chunk
    name, bent_fn, number = BENT[bend]
    monkeypatch.setattr(rtd3, name, bent_fn)
    bent = gaps(s0, s1, td, metrics, ref0, *follow(rtd3, ref0, batches))
    assert bent[number][0] > 10 * bent[number][1], (bend, bent)


def test_a_memory_carried_from_the_row_before_fails_td0(rtd3, chunk, monkeypatch):
    """The memory not reset at a window's start: every row but the first
    starts from a state that is not zero (here the one a row of ones would
    leave: any carried state does)."""
    s0, ref0, s1, td, metrics, batches, _ = chunk
    monkeypatch.setattr(rtd3, "first_state", lambda batch, units: (
        jnp.full((batch, units), 0.5).at[0].set(0.0), jnp.full((batch, units), 0.5).at[0].set(0.0)))
    bent = gaps(s0, s1, td, metrics, ref0, *follow(rtd3, ref0, batches))
    assert bent["td0"][0] > 10 * bent["td0"][1], bent
    # and row 0, whose state was zero, still agrees at update 0
    ref_td0 = follow(rtd3, ref0, batches)[1]["td"][0]
    assert float(jnp.max(jnp.abs(td[0, 0] - ref_td0[0]))) <= 2e-5


def test_work_counts_what_the_issue_counts(rtd3):
    env = {"obs_dim": 54, "act_dim": 21}
    hp = {**HP, "seq_len": 64, "rnn_hidden": 128, "obs_embed": 32, "action_embed": 8, "reward_embed": 8,
          "hidden": [128, 128], "batch_size": 64}
    w = rtd3.work(env, hp)
    per_step = 2 * 64 * 176 * 512  # ISSUE 53: "2 x 64 x 176 x 512 = 11.5 MFLOP a step"
    assert 11.5e6 < per_step < 11.6e6 and w["recur_flops"] == per_step * 65 * rtd3.RECUR_PASSES
    assert 5.5e9 < w["recur_flops"] < 7e9 and 10e9 < w["flops"] < 13e9  # "~6.7" and "~12 GFLOP"
    assert w["row_bytes"] == 64 * 20184.0  # "a row is 5,046 floats = 20,184 B"; "the gather 1.3 MB"
    values = w["state_bytes"] / 32
    assert 0.29e6 < values < 0.32e6  # "actor 0.13 M, critic 0.17 M values"
