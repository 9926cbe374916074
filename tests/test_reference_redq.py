"""REDQ against its plain reference (benchmarks/reference/redq.py), at a small
size on the CPU: the single step, the scan chunk and the chunk on a 2-device
data mesh follow the reference's updates on seeded weights over 2 G + 1
updates, from a first step that is no multiple of G; three references bent on
purpose fail the tolerances the sound one passes; the in-target set and both
normal draws are one stream on both sides; the counters say what the state
says.

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
from distributed_ddpg_tpu.learner import (
    chunk_metrics,
    chunk_noise,
    delayed_updates,
    init_train_state,
    make_learner_step,
    metric_keys,
    noise_base_key,
)
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.types import unpack_batch

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")

ENV = {"obs_dim": 11, "act_dim": 3, "action_scale": 0.4, "action_offset": 0.0}
HP = {
    "hidden": [16, 16], "gamma": 0.99, "tau": 0.005, "actor_lr": 3e-4, "critic_lr": 3e-4,
    "batch_size": 12, "alpha0": 0.2, "critic_ensemble": 5, "target_subset": 2, "policy_delay": 3,
}
G = HP["policy_delay"]
# 2 G + 1 updates from a step that is no multiple of G: the first update skips
# the policy, and the delay's phase is carried into the launch, not restarted.
UPDATES, STEP0, SEED = 2 * G + 1, 4, 11
ROWS, FINAL_SCALE = 64, 200.0


@pytest.fixture(scope="module")
def redq():
    sys.path.insert(0, BENCH)
    try:
        importlib.import_module("reference.sac")  # test_work compares the two counts
        return importlib.import_module("reference.redq")
    finally:
        sys.path.remove(BENCH)


def config(**kw):
    base = dict(
        sac=True, critic_ensemble=HP["critic_ensemble"], target_subset=HP["target_subset"],
        policy_delay=G, actor_hidden=tuple(HP["hidden"]), critic_hidden=tuple(HP["hidden"]),
        batch_size=HP["batch_size"], actor_lr=HP["actor_lr"], critic_lr=HP["critic_lr"], tau=HP["tau"],
        sac_alpha=HP["alpha0"], seed=SEED,
    )
    base.update(kw)
    return DDPGConfig(**base)


def rows(seed, n):
    """Packed rows [obs | action | R | d | next_obs | w], a few of them
    terminal, weights 1."""
    o, a = ENV["obs_dim"], ENV["act_dim"]
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    obs = jax.random.normal(k[0], (n, o))
    disc = HP["gamma"] * (jax.random.uniform(k[3], (n, 1)) > 0.05)
    act = ENV["action_scale"] * jax.random.uniform(k[1], (n, a), minval=-1.0, maxval=1.0)
    return jnp.concatenate(
        [obs, act, jax.random.normal(k[2], (n, 1)), disc, obs + 0.1 * jax.random.normal(k[4], (n, o)),
         jnp.ones((n, 1))], axis=1,
    ).astype(jnp.float32)


def view(state):
    return {"actor": state.actor_params, "critic": state.critic_params,
            "target_critic": state.target_critic_params, "log_alpha": state.log_alpha}


def seeded(redq):
    """The program's and the reference's seeded states, moved to STEP0: the
    counters as a run that has made STEP0 updates would carry them (the
    moments stay zero: both sides start from the same)."""
    cfg = config()
    policy_count = jnp.asarray(delayed_updates(STEP0, G), jnp.int32)
    step0 = jnp.asarray(STEP0, jnp.int32)
    s0 = init_train_state(cfg, ENV["obs_dim"], ENV["act_dim"], SEED)
    s0 = s0._replace(
        step=step0,
        actor_opt=s0.actor_opt._replace(count=policy_count),
        alpha_opt=s0.alpha_opt._replace(count=policy_count),
        critic_opt=s0.critic_opt._replace(count=step0),
    )
    ref0 = redq.init(SEED, ENV, HP)
    ref0["step"] = step0
    ref0["actor_opt"]["count"] = ref0["alpha_opt"]["count"] = policy_count
    ref0["critic_opt"]["count"] = step0
    for k in view(s0):  # the seeded weights: the same keys, the same draws, all five critics
        for a, b in zip(jax.tree.leaves(view(s0)[k]), jax.tree.leaves(ref0[k])):
            np.testing.assert_array_equal(a, b)
    # Seeded final layers are U(+-3e-3): every critic reads a few thousandths
    # and the five lie a ten-thousandth apart, so which of them the target
    # takes, and whether the actor sees their mean or their minimum, would
    # move nothing a tolerance can hold. Both sides' final layers are scaled
    # alike, to values of size 1 a few tenths apart.
    wide = lambda critic: (*critic[:-1], jax.tree.map(lambda x: FINAL_SCALE * x, critic[-1]))
    s0 = s0._replace(critic_params=wide(s0.critic_params), target_critic_params=wide(s0.target_critic_params))
    ref0["critic"], ref0["target_critic"] = wide(ref0["critic"]), wide(ref0["target_critic"])
    return s0, ref0


class Ring:
    """What `run_sample_chunk` needs of a DeviceReplay."""

    def __init__(self, storage):
        self.storage, self.size = storage, jnp.asarray(storage.shape[0], jnp.int32)
        self.dispatch_lock = threading.RLock()

    def device_state(self):
        return self.storage, self.size


def program_chunk(path, s0, storage):
    """(state after, td [K, B], the chunk's metrics, per-update metrics or
    None where the path reports only the chunk's, rows [K, B, width]) from
    the program's own K updates starting at `s0`. `step`: the jitted single
    step, K times, each drawing its own noise. `chunk`: ShardedLearner's
    `_sample_chunk_step` on one device. `mesh2`: the same on a 2-device data
    mesh, global batch unchanged. All three on the rows the learner's key
    draws from `storage`."""
    cfg = config(scale_batch_with_data=False)
    # the rows the chunk draws (parallel/learner.py, draw_chunk_idx)
    sub = jax.random.split(jax.random.PRNGKey(SEED))[1]
    idx = jax.random.randint(sub, (UPDATES, HP["batch_size"]), 0, storage.shape[0])
    batches = storage[idx]
    if path == "step":
        step = jax.jit(make_learner_step(cfg, ENV["action_scale"], action_offset=ENV["action_offset"]))
        s, tds, ms = s0, [], []
        for k in range(UPDATES):
            out = step(s, unpack_batch(batches[k], ENV["obs_dim"], ENV["act_dim"]))
            s = out.state
            tds.append(out.td_errors)
            ms.append(out.metrics)
        per_update = {k: jnp.stack([m[k] for m in ms]) for k in ms[0]}
        return s, jnp.stack(tds), chunk_metrics(per_update), per_update, batches
    devices = jax.devices()[: 2 if path == "mesh2" else 1]
    learner = ShardedLearner(
        cfg, ENV["obs_dim"], ENV["act_dim"], ENV["action_scale"], ENV["action_offset"], chunk_size=UPDATES,
        mesh=mesh_lib.make_mesh(devices=devices),
    )
    assert not learner.fused_chunk_active and learner.global_batch == HP["batch_size"]
    # a copy: the chunk donates its state, and the counters of `seeded` are
    # one array twice
    learner.state = jax.device_put(jax.tree.map(jnp.copy, s0), learner._state_sharding)
    out = learner.run_sample_chunk(Ring(storage))
    return out.state, out.td_errors, out.metrics, None, batches


def follow(redq, ref0, batches):
    step = redq.make_step(SEED, ENV, HP)
    return jax.jit(lambda s, b: jax.lax.scan(step, s, b))(ref0, batches)


def gaps(s0, s1, td, metrics, ref0, ref1, ref):
    """The numbers the comparison is made on, as {name: (value, tolerance)}.
    Both sides are float32 on the CPU, so what is left between a sound
    program and the reference is the order of rounding (the reference
    multiplies at Precision.HIGHEST and evaluates all N targets)."""
    out = {
        # update 0's td, row by row: the forward pass of the policy at s',
        # the drawn targets, their minimum and the N online critics on
        # returns of size 1: a hundred float32 epsilons.
        "td0": (float(jnp.max(jnp.abs(td[0] - ref["td"][0]))), 1e-5),
        # every update's td: seven Adam steps of 3e-4 carry the rounding on.
        # The minimum over all N where the set has two moves it by tenths.
        "td": (float(jnp.max(jnp.abs(td - ref["td"]))), 1e-4),
        "critic_loss": (abs(float(metrics["critic_loss"]) / float(jnp.mean(ref["critic_loss"])) - 1.0), 1e-4),
        # the chunk mean of a loss that reads 0 on the skipped updates
        "actor_loss": (abs(float(metrics["actor_loss"]) - float(jnp.mean(ref["actor_loss"]))), 1e-5),
        "q_spread": (abs(float(metrics["redq_q_spread"]) - float(ref["q_spread"][-1])), 1e-6),
    }
    # every net's change over the chunk, leaf by leaf, to 1% of the leaf's own
    # change or of the net's median leaf's: an actor on the ensemble's minimum
    # takes other Adam steps, a target that waits with the actor goes a third
    # as far.
    after, before = view(s1), view(s0)
    for k in after:
        d_ref = [np.asarray(b1 - b0) for b1, b0 in zip(jax.tree.leaves(ref1[k]), jax.tree.leaves(ref0[k]))]
        d_prog = [np.asarray(a1 - a0) for a1, a0 in zip(jax.tree.leaves(after[k]), jax.tree.leaves(before[k]))]
        floor = np.median([np.linalg.norm(d) for d in d_ref])
        out["change." + k] = (
            max(float(np.linalg.norm(dp - dr) / max(np.linalg.norm(dr), floor)) for dr, dp in zip(d_ref, d_prog)),
            0.01,
        )
    return out


@pytest.fixture(scope="module")
def storage():
    return rows(3, ROWS)


@pytest.fixture(scope="module")
def chunks(redq, storage):
    """Each path's run, made once for the comparisons below."""
    s0, ref0 = seeded(redq)
    return {path: (s0, ref0, *program_chunk(path, s0, storage)) for path in ("step", "chunk", "mesh2")}


@pytest.mark.parametrize("path", ["step", "chunk", "mesh2"])
def test_program_follows_the_reference(redq, chunks, path):
    s0, ref0, s1, td, metrics, per_update, batches = chunks[path]
    ref1, ref = follow(redq, ref0, batches)
    assert set(metrics) == set(metric_keys(config())) and "redq_q_spread" in metrics
    for name, (value, tol) in gaps(s0, s1, td, metrics, ref0, ref1, ref).items():
        assert value <= tol, (name, value, tol)
    # the policy stepped on updates 2 and 5 of these 7 (steps 6 and 9)
    taken = np.asarray(ref["actor_grad_norm"]) > 0
    assert taken.tolist() == [(STEP0 + k) % G == 0 for k in range(UPDATES)] and taken.sum() == 2
    assert float(metrics["actor_grad_norm"]) == pytest.approx(float(jnp.mean(ref["actor_grad_norm"])), rel=1e-3)
    if per_update is not None:
        assert (np.asarray(per_update["actor_grad_norm"]) > 0).tolist() == taken.tolist()
        assert (np.asarray(per_update["actor_loss"]) != 0).tolist() == taken.tolist()
        np.testing.assert_allclose(per_update["redq_q_spread"], ref["q_spread"], atol=1e-6, rtol=0)
        # mean_q here is the ensemble's mean Q(s, a) on the replay rows
        assert np.all(np.isfinite(np.asarray(per_update["mean_q"])))
    # the chunk reports its LAST update's spread, not the mean
    assert abs(float(ref["q_spread"][-1]) - float(jnp.mean(ref["q_spread"]))) > 1e-6
    # the counters: the actor's and the temperature's Adam counts are the
    # record's redq_policy_updates; the critics' is learner_steps
    assert int(s1.step) == STEP0 + UPDATES == int(ref1["step"])
    assert int(s1.critic_opt.count) == STEP0 + UPDATES == int(ref1["critic_opt"]["count"])
    want = delayed_updates(STEP0 + UPDATES, G)
    assert int(s1.actor_opt.count) == int(s1.alpha_opt.count) == want == 4
    assert int(ref1["actor_opt"]["count"]) == int(ref1["alpha_opt"]["count"]) == want


def test_the_single_step_and_the_chunk_draw_the_same(chunks):
    """The single step draws for itself what the chunk draws in front of its
    scan: the same noise and the same set, so the same state, to the order of
    XLA:CPU's fusions (another set on one update moves td by tenths)."""
    for a, b in zip(jax.tree.leaves(view(chunks["step"][2])), jax.tree.leaves(view(chunks["chunk"][2]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(chunks["step"][3]), np.asarray(chunks["chunk"][3]), rtol=0, atol=1e-6)


BENT = {
    # the minimum over all N targets where Algorithm 1 draws M of them
    # (update 0 of this chunk happens to draw the critic that is lowest on
    # every row, so td0 alone would not see it; four of the seven do not)
    "minimum_over_all": ("in_target_value", lambda next_q, subset: jnp.min(next_q, axis=0), {"td", "critic_loss"}),
    # the actor on the ensemble's minimum (SAC's) where Algorithm 1 has the mean
    "actor_on_the_minimum": ("policy_value", lambda q: jnp.min(q, axis=0), {"change.actor", "actor_loss"}),
    # targets that wait with the actor (TD3's) where REDQ's move on every update
    "targets_wait": ("targets_move", lambda policy_steps: policy_steps, {"change.target_critic"}),
}


@pytest.mark.parametrize("bend", sorted(BENT))
def test_a_bent_reference_fails_where_the_sound_one_passes(redq, chunks, monkeypatch, bend):
    s0, ref0, s1, td, metrics, _, batches = chunks["chunk"]
    sound = gaps(s0, s1, td, metrics, ref0, *follow(redq, ref0, batches))
    assert all(value <= tol for value, tol in sound.values())
    name, bent_fn, must_fail = BENT[bend]
    monkeypatch.setattr(redq, name, bent_fn)
    bent = gaps(s0, s1, td, metrics, ref0, *follow(redq, ref0, batches))
    failed = {k for k, (value, tol) in bent.items() if value > tol}
    assert must_fail <= failed, (bend, bent)
    # and by a margin: the first named number at ten times its tolerance
    first = sorted(must_fail)[0]
    assert bent[first][0] > 10 * bent[first][1], (bend, first, bent[first])


@pytest.mark.parametrize("seed,step0", [(0, 0), (11, 4), (2_147_483_659, 800)])
def test_the_reference_draws_the_programs_streams(redq, seed, step0):
    """`redq.draws` from the reference's key, update by update, against
    `learner.chunk_noise`, what the scan chunk scans over: the same normals
    and the same set, for a seed past 2**31 too."""
    cfg = config().replace(seed=seed)
    b, a, k = HP["batch_size"], ENV["act_dim"], 5
    ours = chunk_noise(cfg, noise_base_key(cfg), jnp.asarray(step0, jnp.int32), k, b, a)
    assert len(ours) == 3 and ours[2].shape == (k, HP["target_subset"]) and ours[2].dtype == jnp.int32
    key = redq.init(seed, ENV, HP)["noise_key"]
    theirs = [redq.draws(key, jnp.asarray(step0 + i, jnp.int32), HP, (b, a)) for i in range(k)]
    for member in range(3):
        np.testing.assert_array_equal(np.asarray(ours[member]), np.stack([np.asarray(t[member]) for t in theirs]))


def test_work_counts_the_algorithm_over_the_policys_period(redq):
    """Matmul operations of one mean update at the paper's sizes: ten critics
    forward and backward on every update, two target passes, the actor at s'
    on every update and its step, through all ten critics, on one in twenty."""
    env = {"obs_dim": 376, "act_dim": 17}
    hp = {**HP, "hidden": [256, 256], "batch_size": 256, "critic_ensemble": 10, "target_subset": 2,
          "policy_delay": 20}
    w = redq.work(env, hp)
    f_actor = 2.0 * 256 * (376 * 256 + 256 * 256 + 256 * 34)
    f_critic = 2.0 * 256 * (376 * 256 + 273 * 256 + 256 * 1)
    assert w["flops"] == pytest.approx((1 + 3 / 20) * f_actor + (30 + 2 + 30 / 20) * f_critic)
    assert 2.8e9 < w["flops"] < 3.0e9
    assert w["row_bytes"] == 4.0 * 256 * (2 * 376 + 17 + 3)
    one_critic = 376 * 256 + 256 + 273 * 256 + 256 + 256 + 1
    assert one_critic == 166_913
    actor = 376 * 256 + 256 + 256 * 256 + 256 + 256 * 34 + 34
    assert w["state_bytes"] == 2.0 * 4 * 4 * (actor + 10 * one_critic)
    # N = M = 2 with the policy on every update is what reference/sac.py counts
    plain = redq.work(env, {**hp, "critic_ensemble": 2, "target_subset": 2, "policy_delay": 1})
    assert plain["flops"] == pytest.approx(4 * f_actor + 14 * f_critic)
    assert plain == sys.modules["reference.sac"].work(env, hp)
