"""CrossQ against its plain reference (benchmarks/reference/crossq.py), at a
small size on the CPU: the single step, the scan chunk and the chunk on a
2-device data mesh follow the reference's updates on seeded weights over
2 * 3 + 1 updates, from a first step off the delay's phase; five references
bent on purpose fail the tolerances the sound one passes; both normal draws
are one stream on both sides; under an explicit data axis the replicas'
statistics stay identical.

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
    LAST_UPDATE_KEYS,
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
    "critic_hidden": [32, 32], "actor_hidden": [16, 16], "gamma": 0.99, "actor_lr": 1e-3, "critic_lr": 1e-3,
    "batch_size": 16, "alpha0": 0.2, "policy_delay": 3, "adam_b1": 0.5, "bn_momentum": 0.99, "bn_eps": 1e-3,
}
G = HP["policy_delay"]
# 2 G + 1 updates from a step that is no multiple of G: the first update skips
# the policy, and the delay's phase is carried into the launch, not restarted.
UPDATES, STEP0, SEED = 2 * G + 1, 4, 11
ROWS, FINAL_SCALE = 64, 200.0


@pytest.fixture(scope="module")
def crossq():
    sys.path.insert(0, BENCH)
    try:
        return importlib.import_module("reference.crossq")
    finally:
        sys.path.remove(BENCH)


def config(**kw):
    base = dict(
        sac=True, crossq=True, policy_delay=G, adam_b1=HP["adam_b1"], action_insert_layer=0,
        actor_hidden=tuple(HP["actor_hidden"]), critic_hidden=tuple(HP["critic_hidden"]),
        batch_size=HP["batch_size"], actor_lr=HP["actor_lr"], critic_lr=HP["critic_lr"],
        sac_alpha=HP["alpha0"], seed=SEED,
    )
    base.update(kw)
    return DDPGConfig(**base)


def rows(seed, n):
    """Packed rows [obs | action | R | d | next_obs | w], a few of them
    terminal, weights 1; the observation's columns on scales from a tenth to
    ten, so that the input's normalisation has something to do."""
    o, a = ENV["obs_dim"], ENV["act_dim"]
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    spread = jnp.logspace(-1.0, 1.0, o)
    obs = 0.5 * spread + spread * jax.random.normal(k[0], (n, o))
    disc = HP["gamma"] * (jax.random.uniform(k[3], (n, 1)) > 0.05)
    act = ENV["action_scale"] * jax.random.uniform(k[1], (n, a), minval=-1.0, maxval=1.0)
    return jnp.concatenate(
        [obs, act, jax.random.normal(k[2], (n, 1)), disc, obs + 0.1 * spread * jax.random.normal(k[4], (n, o)),
         jnp.ones((n, 1))], axis=1,
    ).astype(jnp.float32)


def view(state):
    return {"actor": state.actor_params, "critic": state.critic_params, "log_alpha": state.log_alpha}


def seeded(crossq):
    """The program's and the reference's seeded states, moved to STEP0: the
    counters as a run that has made STEP0 updates would carry them (the
    moments stay zero: both sides start from the same)."""
    cfg = config()
    policy_count = jnp.asarray(delayed_updates(STEP0, G), jnp.int32)
    step0 = jnp.asarray(STEP0, jnp.int32)
    s0 = init_train_state(cfg, ENV["obs_dim"], ENV["act_dim"], SEED)
    assert s0.target_actor_params is None and s0.target_critic_params is None
    s0 = s0._replace(
        step=step0,
        actor_opt=s0.actor_opt._replace(count=policy_count),
        alpha_opt=s0.alpha_opt._replace(count=policy_count),
        critic_opt=s0.critic_opt._replace(count=step0),
    )
    ref0 = crossq.init(SEED, ENV, HP)
    assert not [k for k in ref0 if k.startswith("target")]
    ref0["step"] = step0
    ref0["actor_opt"]["count"] = ref0["alpha_opt"]["count"] = policy_count
    ref0["critic_opt"]["count"] = step0
    for k in view(s0):  # the same tree, leaf for leaf: the same keys, the same draws
        assert jax.tree.structure(view(s0)[k]) == jax.tree.structure(ref0[k])
        for a, b in zip(jax.tree.leaves(view(s0)[k]), jax.tree.leaves(ref0[k])):
            np.testing.assert_array_equal(a, b)
    # Seeded final layers are U(+-3e-3): both critics read a few thousandths
    # a ten-thousandth apart, and nothing a tolerance can hold would tell
    # which one the minimum took. Both sides' final layers are scaled alike.
    wide = lambda critic: (
        *critic[:-1], {**critic[-1], **{k: FINAL_SCALE * critic[-1][k] for k in ("w", "b")}}
    )
    s0 = s0._replace(critic_params=wide(s0.critic_params))
    ref0["critic"] = wide(ref0["critic"])
    return s0, ref0


class Ring:
    """What `run_sample_chunk` needs of a DeviceReplay."""

    def __init__(self, storage):
        self.storage, self.size = storage, jnp.asarray(storage.shape[0], jnp.int32)
        self.dispatch_lock = threading.RLock()

    def device_state(self):
        return self.storage, self.size


def chunk_learner(devices, mode="auto"):
    learner = ShardedLearner(
        config(scale_batch_with_data=False), ENV["obs_dim"], ENV["act_dim"], ENV["action_scale"],
        ENV["action_offset"], chunk_size=UPDATES, mesh=mesh_lib.make_mesh(devices=devices), mode=mode,
    )
    assert not learner.fused_chunk_active and learner.global_batch == HP["batch_size"]
    return learner


def program_chunk(path, s0, storage):
    """(state after, td [K, B], the chunk's metrics, per-update metrics or
    None where the path reports only the chunk's, rows [K, B, width]) from
    the program's own K updates starting at `s0`. `step`: the jitted single
    step, K times, each drawing its own noise. `chunk`: ShardedLearner's
    sampling chunk on one device. `mesh2`: the same on a 2-device data mesh,
    global batch unchanged, the partitioner's collectives. All three on the
    rows the learner's key draws from `storage`."""
    cfg = config(scale_batch_with_data=False)
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
    learner = chunk_learner(jax.devices()[: 2 if path == "mesh2" else 1])
    # a copy: the chunk donates its state
    learner.state = jax.device_put(jax.tree.map(jnp.copy, s0), learner._state_sharding)
    out = learner.run_sample_chunk(Ring(storage))
    return out.state, out.td_errors, out.metrics, None, batches


def follow(crossq, ref0, batches):
    step = crossq.make_step(SEED, ENV, HP)
    return jax.jit(lambda s, b: jax.lax.scan(step, s, b))(ref0, batches)


def gaps(s0, s1, td, metrics, ref0, ref1, ref):
    """The numbers the comparison is made on, as {name: (value, tolerance)}.
    Both sides are float32 on the CPU, so what is left between a sound
    program and the reference is the order of rounding (the reference
    multiplies at Precision.HIGHEST, concatenates where the program stacks
    and divides where the program multiplies by a reciprocal root)."""
    out = {
        # update 0's td, row by row: the policy at s' in evaluation mode and
        # the joint pass of both critics on returns of size 1
        "td0": (float(jnp.max(jnp.abs(td[0] - ref["td"][0]))), 2e-5),
        # every update's td: seven Adam steps of 1e-3 carry the rounding on
        "td": (float(jnp.max(jnp.abs(td - ref["td"]))), 1e-3),
        "critic_loss": (abs(float(metrics["critic_loss"]) / float(jnp.mean(ref["critic_loss"])) - 1.0), 1e-3),
        # the chunk mean of a loss that reads 0 on the skipped updates
        "actor_loss": (abs(float(metrics["actor_loss"]) - float(jnp.mean(ref["actor_loss"]))), 1e-4),
        "bn_stat_gap": (abs(float(metrics["bn_stat_gap"]) - float(ref["bn_stat_gap"][-1])), 1e-5),
    }
    # every net's change over the chunk, leaf by leaf (the running statistics
    # are leaves like the weights), to 2% of the leaf's own change or of the
    # net's median leaf's
    after, before = view(s1), view(s0)
    for k in after:
        d_ref = [np.asarray(b1 - b0) for b1, b0 in zip(jax.tree.leaves(ref1[k]), jax.tree.leaves(ref0[k]))]
        d_prog = [np.asarray(a1 - a0) for a1, a0 in zip(jax.tree.leaves(after[k]), jax.tree.leaves(before[k]))]
        floor = np.median([np.linalg.norm(d) for d in d_ref])
        out["change." + k] = (
            max(float(np.linalg.norm(dp - dr) / max(np.linalg.norm(dr), floor)) for dr, dp in zip(d_ref, d_prog)),
            0.02,
        )
    return out


@pytest.fixture(scope="module")
def storage():
    return rows(3, ROWS)


@pytest.fixture(scope="module")
def chunks(crossq, storage):
    """Each path's run, made once for the comparisons below."""
    s0, ref0 = seeded(crossq)
    return {path: (s0, ref0, *program_chunk(path, s0, storage)) for path in ("step", "chunk", "mesh2")}


@pytest.mark.parametrize("path", ["step", "chunk", "mesh2"])
def test_program_follows_the_reference(crossq, chunks, path):
    s0, ref0, s1, td, metrics, per_update, batches = chunks[path]
    ref1, ref = follow(crossq, ref0, batches)
    assert set(metrics) == set(metric_keys(config())) and "bn_stat_gap" in metrics
    for name, (value, tol) in gaps(s0, s1, td, metrics, ref0, ref1, ref).items():
        assert value <= tol, (name, value, tol)
    # no target exists, before or after
    assert s1.target_actor_params is None and s1.target_critic_params is None
    # the policy stepped on updates 2 and 5 of these 7 (steps 6 and 9)
    taken = np.asarray(ref["actor_grad_norm"]) > 0
    assert taken.tolist() == [(STEP0 + k) % G == 0 for k in range(UPDATES)] and taken.sum() == 2
    assert float(metrics["actor_grad_norm"]) == pytest.approx(float(jnp.mean(ref["actor_grad_norm"])), rel=1e-3)
    if per_update is not None:
        assert (np.asarray(per_update["actor_grad_norm"]) > 0).tolist() == taken.tolist()
        assert (np.asarray(per_update["actor_loss"]) != 0).tolist() == taken.tolist()
        np.testing.assert_allclose(per_update["bn_stat_gap"], ref["bn_stat_gap"], atol=1e-5, rtol=0)
        assert np.all(np.isfinite(np.asarray(per_update["mean_q"])))
    # the chunk reports its LAST update's gap, not the mean
    assert "bn_stat_gap" in LAST_UPDATE_KEYS
    assert abs(float(ref["bn_stat_gap"][-1]) - float(jnp.mean(ref["bn_stat_gap"]))) > 1e-5
    # the counters: the actor's and the temperature's Adam counts are the
    # record's crossq_policy_updates; the critics' is learner_steps
    assert int(s1.step) == STEP0 + UPDATES == int(ref1["step"])
    assert int(s1.critic_opt.count) == STEP0 + UPDATES == int(ref1["critic_opt"]["count"])
    want = delayed_updates(STEP0 + UPDATES, G)
    assert int(s1.actor_opt.count) == int(s1.alpha_opt.count) == want == 4
    assert int(ref1["actor_opt"]["count"]) == int(ref1["alpha_opt"]["count"]) == want
    # Adam never touched a statistic: its moments there are zero to the bit
    for opt in (s1.actor_opt, s1.critic_opt):
        for layer_mu, layer_nu in zip(opt.mu, opt.nu):
            for k in ("bn_mean", "bn_var"):
                assert not np.any(np.asarray(layer_mu[k])) and not np.any(np.asarray(layer_nu[k]))
    # the actor's statistics moved (on the policy's two updates; how far, the
    # reference holds above, leaf by leaf)
    assert np.any(np.asarray(s1.actor_params[0]["bn_var"]) != 1.0)


def test_the_single_step_and_the_chunk_draw_the_same(chunks):
    """The single step draws for itself what the chunk draws in front of its
    scan: the same noise, so the same state, to the order of XLA:CPU's
    fusions."""
    for a, b in zip(jax.tree.leaves(view(chunks["step"][2])), jax.tree.leaves(view(chunks["chunk"][2]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=2e-6)
    np.testing.assert_allclose(np.asarray(chunks["step"][3]), np.asarray(chunks["chunk"][3]), rtol=0, atol=1e-5)


def separate_passes(critics, obs, action, next_obs, next_action):
    """Each half through its own training-mode pass, so with its own batch
    statistics: what the paper's ablation shows failing."""
    q, moments = critics(jnp.concatenate([obs, action], -1), True)
    next_q, _ = critics(jnp.concatenate([next_obs, next_action], -1), True)
    return q, next_q, moments


def evaluation_mode(critics, obs, action, next_obs, next_action):
    """The joint rows through the running statistics."""
    x = jnp.concatenate([jnp.concatenate([obs, action], -1), jnp.concatenate([next_obs, next_action], -1)])
    q, moments = critics(x, False)
    return q[:, : obs.shape[0]], q[:, obs.shape[0] :], moments


BENT = {
    "separate_passes": ("joint_values", lambda frozen: separate_passes, {"td", "critic_loss", "change.critic"}),
    "evaluation_mode_in_the_critic_loss": (
        "joint_values", lambda frozen: evaluation_mode, {"td", "critic_loss", "change.critic"}),
    # a target network read in the Bellman target: the seeded critics, which a
    # Polyak target of rate 0.005 is to within 3% over these seven updates
    "a_target_network_in_the_bellman_target": (
        "bootstrap", lambda frozen: (lambda next_q, evaluate: evaluate(frozen)), {"td", "critic_loss"}),
    "adam_b1_0.9": ("adam_b1", lambda frozen: (lambda hp: 0.9), {"change.critic", "td"}),
    "the_policy_on_every_update": (
        "policy_steps", lambda frozen: (lambda step, hp: True), {"change.actor", "actor_loss"}),
}


@pytest.mark.parametrize("bend", sorted(BENT))
def test_a_bent_reference_fails_where_the_sound_one_passes(crossq, chunks, monkeypatch, bend):
    s0, ref0, s1, td, metrics, _, batches = chunks["chunk"]
    sound = gaps(s0, s1, td, metrics, ref0, *follow(crossq, ref0, batches))
    assert all(value <= tol for value, tol in sound.values())
    name, bent_fn, must_fail = BENT[bend]
    monkeypatch.setattr(crossq, name, bent_fn(ref0["critic"]))
    bent = gaps(s0, s1, td, metrics, ref0, *follow(crossq, ref0, batches))
    failed = {k for k, (value, tol) in bent.items() if value > tol}
    assert must_fail <= failed, (bend, bent)
    # and by a margin: the first named number at ten times its tolerance
    first = sorted(must_fail)[0]
    assert bent[first][0] > 10 * bent[first][1], (bend, first, bent[first])


@pytest.mark.parametrize("seed,step0", [(0, 0), (11, 4), (2_147_483_659, 800)])
def test_the_reference_draws_the_programs_streams(crossq, seed, step0):
    """`crossq.draws` from the reference's key, update by update, against
    `learner.chunk_noise`, what the scan chunk scans over: SAC's two normal
    streams and no third member, for a seed past 2**31 too."""
    cfg = config().replace(seed=seed)
    b, a, k = HP["batch_size"], ENV["act_dim"], 5
    ours = chunk_noise(cfg, noise_base_key(cfg), jnp.asarray(step0, jnp.int32), k, b, a)
    assert len(ours) == 2
    key = crossq.init(seed, ENV, HP)["noise_key"]
    theirs = [crossq.draws(key, jnp.asarray(step0 + i, jnp.int32), HP, (b, a)) for i in range(k)]
    for member in range(2):
        np.testing.assert_array_equal(np.asarray(ours[member]), np.stack([np.asarray(t[member]) for t in theirs]))


def test_work_counts_the_algorithm_over_the_policys_period(crossq):
    """Matmul operations of one mean update at the paper's sizes: the joint
    pass on 512 rows forward and backward through both 2x2048 critics with no
    input gradient through the first layer, the actor at s' on every update,
    and the policy's step, through evaluation-mode critics, on one in three."""
    env = {"obs_dim": 376, "act_dim": 17}
    hp = {**HP, "critic_hidden": [2048, 2048], "actor_hidden": [256, 256], "batch_size": 256}
    w = crossq.work(env, hp)
    s_c, t_c = 393 * 2048 + 2048 * 2048 + 2048, 2048 * 2048 + 2048
    s_a, t_a = 376 * 256 + 256 * 256 + 256 * 34, 256 * 256 + 256 * 34
    every = 2 * 256 * s_a + 2 * 2 * 512 * (2 * s_c + t_c)
    policy = 2 * 256 * (2 * s_a + t_a) + 2 * 2 * 256 * (s_c + t_c + 17 * 2048)
    assert w["flops"] == pytest.approx(every + policy / 3)
    assert 32.0e9 < w["flops"] < 32.8e9  # ISSUE 38's "~32 GFLOP"
    assert w["row_bytes"] == 4.0 * 256 * (2 * 376 + 17 + 3)
    one_critic = s_c + (2048 + 2048 + 1) + 4 * (393 + 2048 + 2048)
    actor = s_a + (256 + 256 + 34) + 4 * (376 + 256 + 256)
    assert w["state_bytes"] == 2.0 * 4 * 3 * (actor + 2 * one_critic)  # no target: three copies, not four
    # and the count is the program's own state, value for value
    cfg = config(critic_hidden=(2048, 2048), actor_hidden=(256, 256), batch_size=256)
    state = jax.eval_shape(lambda: init_train_state(cfg, 376, 17, 0))
    values = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves((state.actor_params, state.critic_params)))
    assert values == actor + 2 * one_critic and 10.0e6 < values < 10.3e6


def test_replicas_statistics_stay_identical_under_the_explicit_data_axis(storage, crossq):
    """Explicit mode (shard_map, per-step pmean): each replica normalises by
    the GLOBAL batch's moments (two pmeans a layer), so its running
    statistics, like its weights, are every replica's; and they are the
    moments of all 2B rows, not of a shard's: against the one-device chunk on
    the same rows, where the replicas draw other noise, BN_0's statistics of
    the observation's columns (which no noise reaches) agree."""
    s0, _ = seeded(crossq)
    ends = {}
    for name, devices, mode in (("one", jax.devices()[:1], "auto"), ("explicit2", jax.devices()[:2], "explicit")):
        learner = chunk_learner(devices, mode)
        learner.state = jax.device_put(jax.tree.map(jnp.copy, s0), learner._state_sharding)
        ends[name] = learner.run_sample_chunk(Ring(storage)).state
    for leaf in jax.tree.leaves((ends["explicit2"].actor_params, ends["explicit2"].critic_params)):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(shards) == 2
        np.testing.assert_array_equal(shards[0], shards[1])
    o = ENV["obs_dim"]
    for k in ("bn_mean", "bn_var"):
        one, two = (np.asarray(ends[n].critic_params[0][k])[:, :o] for n in ("one", "explicit2"))
        np.testing.assert_allclose(one, two, rtol=2e-5, atol=1e-6)
        assert np.any(np.abs(one - (0.0 if k == "bn_mean" else 1.0)) > 1e-3)
