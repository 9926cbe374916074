"""DMPO against its plain reference (benchmarks/reference/dmpo.py), at a small
size on the CPU: the single step, the scan chunk (what `train()` launches),
the chunk on the 8-device data mesh and the explicit shard_map step follow
the reference's updates over 1 and over 8 updates, which cross two copy
periods of 3, duals included; references bent on purpose (a wrong
epsilon_mean, coupled KLs, no action penalty, Polyak for the copy, one sample
fewer, the mean of the samples' logits where their mixture belongs, float8
products) each fail a stated number that the sound one passes.

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
    MPO_KEYS,
    chunk_metrics,
    init_train_state,
    make_learner_step,
    metric_keys,
)
from distributed_ddpg_tpu.ops import losses
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.types import unpack_batch

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")

ENV = {"obs_dim": 11, "act_dim": 3, "action_scale": 0.4, "action_offset": 0.1}
# The source's rates are 1e-4 and its duals start at log 10 / 10 / 1000,
# where softplus is the identity, the temperature is ten times any seeded
# value and eight updates move nothing a tolerance can see. Ten times the
# rates, duals that start near 1 and a period of 3 make every part of the
# update visible in 8: weights off uniform, KLs at the size of their bounds,
# two copies of the targets.
HP = {
    "actor_hidden": [16, 16, 16], "critic_hidden": [32, 32, 16], "gamma": 0.99, "actor_lr": 1e-3,
    "critic_lr": 1e-3, "dual_lr": 1e-2, "batch_size": 16, "num_atoms": 11, "v_min": -5.0, "v_max": 5.0,
    "samples": 5, "epsilon": 0.1, "epsilon_penalty": 1e-3, "epsilon_mean": 2.5e-3, "epsilon_stddev": 1e-6,
    "init_log_temperature": -3.0, "init_log_alpha_mean": 1.0, "init_log_alpha_stddev": 10.0,
    "target_update_period": 3,
}
UPDATES, SEED, ROWS, POLICY_SCALE, CRITIC_SCALE = 8, 11, 64, 300.0, 1500.0
_FOLLOWERS = {}  # jitted programs the cases share: the single step, the reference's scans


@pytest.fixture(scope="module")
def dmpo():
    sys.path.insert(0, BENCH)
    try:
        return importlib.import_module("reference.dmpo")
    finally:
        sys.path.remove(BENCH)


def config(**kw):
    base = dict(
        mpo=True, distributional=True, action_insert_layer=0, n_step=5, num_atoms=HP["num_atoms"],
        v_min=HP["v_min"], v_max=HP["v_max"], actor_hidden=tuple(HP["actor_hidden"]),
        critic_hidden=tuple(HP["critic_hidden"]), batch_size=HP["batch_size"], actor_lr=HP["actor_lr"],
        critic_lr=HP["critic_lr"], dual_lr=HP["dual_lr"], mpo_samples=HP["samples"],
        mpo_epsilon=HP["epsilon"], mpo_epsilon_penalty=HP["epsilon_penalty"],
        mpo_epsilon_mean=HP["epsilon_mean"], mpo_epsilon_stddev=HP["epsilon_stddev"],
        mpo_init_log_temperature=HP["init_log_temperature"],
        mpo_init_log_alpha_mean=HP["init_log_alpha_mean"],
        mpo_init_log_alpha_stddev=HP["init_log_alpha_stddev"],
        target_update_period=HP["target_update_period"], seed=SEED, scale_batch_with_data=False,
    )
    base.update(kw)
    return DDPGConfig(**base)


def rows(seed, n):
    """Packed rows [obs | action | R | d | next_obs | w], a few terminal,
    weights 1, the action inside the environment's box 0.1 +- 0.4."""
    o, a = ENV["obs_dim"], ENV["act_dim"]
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    obs = jax.random.normal(k[0], (n, o))
    act = ENV["action_offset"] + ENV["action_scale"] * jax.random.uniform(k[1], (n, a), minval=-1.0, maxval=1.0)
    disc = HP["gamma"] ** 5 * (jax.random.uniform(k[3], (n, 1)) > 0.05)
    return jnp.concatenate(
        [obs, act, jax.random.normal(k[2], (n, 1)), disc, obs + 0.1 * jax.random.normal(k[4], (n, o)),
         jnp.ones((n, 1))], axis=1,
    ).astype(jnp.float32)


def view(state):
    return {"actor": state.actor_params, "critic": state.critic_params,
            "target_actor": state.target_actor_params, "target_critic": state.target_critic_params,
            "log_alpha": state.log_alpha}


def seeded(dmpo):
    """The program's and the reference's seeded states: equal to the last
    bit, leaf for leaf, the dual variables' tree among them. Seeded output
    layers are U(+-3e-3): every sampled action would read the same value to
    a ten-thousandth and every weight 1/N whatever the code did, so both
    sides' output layers are scaled alike (the policy's too: its mean then
    leaves the box on some rows, and the penalty has something to weigh; the
    critic's logits a few units wide, so that the samples' mixture and the
    distribution of their mean logits are two distributions)."""
    s0 = init_train_state(config(), ENV["obs_dim"], ENV["act_dim"], SEED)
    ref0 = dmpo.init(SEED, ENV, HP)
    for k in view(s0):
        assert jax.tree.structure(view(s0)[k]) == jax.tree.structure(ref0[k])
        for a, b in zip(jax.tree.leaves(view(s0)[k]), jax.tree.leaves(ref0[k])):
            np.testing.assert_array_equal(a, b)
    wide = lambda net, by: (*net[:-1], {k: by * v for k, v in net[-1].items()})
    s0 = s0._replace(
        actor_params=wide(s0.actor_params, POLICY_SCALE), target_actor_params=wide(s0.target_actor_params, POLICY_SCALE),
        critic_params=wide(s0.critic_params, CRITIC_SCALE), target_critic_params=wide(s0.target_critic_params, CRITIC_SCALE),
    )
    ref0["actor"] = ref0["target_actor"] = wide(ref0["actor"], POLICY_SCALE)
    ref0["critic"] = ref0["target_critic"] = wide(ref0["critic"], CRITIC_SCALE)
    return s0, ref0


class Ring:
    """What `run_sample_chunk` needs of a DeviceReplay."""

    def __init__(self, storage):
        self.storage, self.size = storage, jnp.asarray(storage.shape[0], jnp.int32)
        self.dispatch_lock = threading.RLock()

    def device_state(self):
        return self.storage, self.size


def chunk_learner(devices, updates, mode="auto", **kw):
    learner = ShardedLearner(
        config(**kw), ENV["obs_dim"], ENV["act_dim"], ENV["action_scale"], ENV["action_offset"],
        chunk_size=updates, mesh=mesh_lib.make_mesh(devices=devices), mode=mode,
    )
    assert not learner.fused_chunk_active and learner.global_batch == HP["batch_size"]
    return learner


def program_chunk(path, s0, storage, updates):
    """(state after, td [K, B], the chunk's metrics, rows [K, B, width]) of
    the program's own K updates from `s0`: `step` the jitted single step K
    times (it draws its own noise), `chunk` ShardedLearner's sampling chunk
    on one device (the noise drawn in front of the scan), `mesh8` the same
    on the 8 virtual devices' data mesh at the same global batch."""
    sub = jax.random.split(jax.random.PRNGKey(SEED))[1]
    batches = storage[jax.random.randint(sub, (updates, HP["batch_size"]), 0, storage.shape[0])]
    if path == "step":
        if "step" not in _FOLLOWERS:
            _FOLLOWERS["step"] = jax.jit(
                make_learner_step(config(), ENV["action_scale"], action_offset=ENV["action_offset"])
            )
        step = _FOLLOWERS["step"]
        s, tds, ms = s0, [], []
        for k in range(updates):
            out = step(s, unpack_batch(batches[k], ENV["obs_dim"], ENV["act_dim"]))
            s = out.state
            tds.append(out.td_errors)
            ms.append(out.metrics)
        return s, jnp.stack(tds), chunk_metrics({k: jnp.stack([m[k] for m in ms]) for k in ms[0]}), batches
    learner = chunk_learner(jax.devices()[: 8 if path == "mesh8" else 1], updates)
    learner.state = jax.device_put(jax.tree.map(jnp.copy, s0), learner._state_sharding)  # the chunk donates
    out = learner.run_sample_chunk(Ring(storage))
    return out.state, out.td_errors, out.metrics, batches


def follow(dmpo, ref0, batches, hp=HP, operand_dtype=None, bend=None):
    """The reference's own updates on `batches`. One jitted scan a bend (the
    sound reference is `None`), traced while the bend's patch is in place
    and kept: the sound one serves every path and every bent case."""
    if bend not in _FOLLOWERS:
        step = dmpo.make_step(SEED, ENV, hp, operand_dtype)
        _FOLLOWERS[bend] = jax.jit(lambda s, b: jax.lax.scan(step, s, b))
    return _FOLLOWERS[bend](ref0, batches)


def gaps(s0, s1, td, metrics, ref0, ref1, ref):
    """The numbers the comparison is made on, as {name: (value, tolerance)}.
    Both sides are float32 on the CPU, so what is left between a sound
    program and the reference is the order of rounding."""
    out = {
        # update 0's td, row by row: the E-step, the mixture, the projection
        # and the critic's forward pass on seeded weights, on returns of size 1
        "td0": (float(jnp.max(jnp.abs(td[0] - ref["td"][0]))), 2e-5),
        "td": (float(jnp.max(jnp.abs(td - ref["td"]))), 2e-4),
        "critic_loss": (abs(float(metrics["critic_loss"]) / float(jnp.mean(ref["critic_loss"])) - 1.0), 1e-4),
        "actor_loss": (abs(float(metrics["actor_loss"]) / float(jnp.mean(ref["actor_loss"])) - 1.0), 1e-4),
        # the chunk's LAST update's
        "edge_mass": (abs(float(metrics["c51_edge_mass"]) - float(ref["edge_mass"][-1])), 1e-5),
        "weight_ess": (abs(float(metrics["mpo_weight_ess"]) - float(ref["weight_ess"][-1])), 1e-3),
        # 0 on an update that follows a copy: relative to its size or to a thousandth
        "kl_mean_ratio": (
            abs(float(metrics["mpo_kl_mean_ratio"]) - float(ref["kl_mean_ratio"][-1]))
            / max(float(ref["kl_mean_ratio"][-1]), 1e-3), 1e-2,
        ),
        "temperature": (abs(float(metrics["mpo_temperature"]) - float(ref["temperature"][-1])), 1e-5),
    }
    # every net's change over the chunk and the duals', leaf by leaf, to 1%
    # of the leaf's own change or of the tree's median leaf's
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


@pytest.fixture(scope="module")
def storage():
    return rows(3, ROWS)


@pytest.fixture(scope="module")
def chunks(dmpo, storage):
    """Each path's run, made once for the comparisons below."""
    s0, ref0 = seeded(dmpo)
    runs = {(path, UPDATES): (s0, ref0, *program_chunk(path, s0, storage, UPDATES)) for path in ("step", "chunk", "mesh8")}
    runs[("chunk", 1)] = (s0, ref0, *program_chunk("chunk", s0, storage, 1))
    return runs


@pytest.mark.parametrize("path,updates", [("step", UPDATES), ("chunk", 1), ("chunk", UPDATES), ("mesh8", UPDATES)])
def test_program_follows_the_reference(dmpo, chunks, path, updates):
    s0, ref0, s1, td, metrics, batches = chunks[(path, updates)]
    ref1, ref = follow(dmpo, ref0, batches)
    assert set(metrics) == set(metric_keys(config())) and set(MPO_KEYS) <= set(LAST_UPDATE_KEYS)
    for name, (value, tol) in gaps(s0, s1, td, metrics, ref0, ref1, ref).items():
        assert value <= tol, (name, value, tol)
    assert int(s1.step) == updates == int(ref1["step"])
    if updates == UPDATES:
        # the weights are off uniform, the mean's KL is at its bound's size,
        # and the last copy (after update 5) is two updates old
        assert float(metrics["mpo_weight_ess"]) < HP["samples"] - 0.2
        assert 0.05 < float(metrics["mpo_kl_mean_ratio"]) < 50
        assert any(
            not np.array_equal(a, b)
            for a, b in zip(jax.tree.leaves(s1.actor_params), jax.tree.leaves(s1.target_actor_params))
        )
    # Adam moved every dual variable, the two temperatures f32[1] among them
    assert set(s1.log_alpha) == set(losses.MPO_DUALS) and s1.log_alpha["log_temperature"].shape == (1,)
    for name in losses.MPO_DUALS:
        assert not np.array_equal(s1.log_alpha[name], s0.log_alpha[name]), name
    assert int(s1.alpha_opt.count) == updates


def test_targets_are_the_online_nets_at_the_last_copy(dmpo, chunks):
    """Six updates end two periods of 3: the targets then ARE the online
    nets, to the bit, and one update later they are what they were."""
    s0, ref0, _, _, _, _ = chunks[("chunk", UPDATES)]
    storage = rows(3, ROWS)
    at6 = program_chunk("step", s0, storage, 6)[0]
    at7 = program_chunk("step", s0, storage, 7)[0]
    for tree6, target6, target7 in (
        (at6.actor_params, at6.target_actor_params, at7.target_actor_params),
        (at6.critic_params, at6.target_critic_params, at7.target_critic_params),
    ):
        for a, b, c in zip(*map(jax.tree.leaves, (tree6, target6, target7))):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(b, c)


# What each fault moves, by a stated number: the sound program reads under
# the tolerance, the bent reference over ten times it. `hp` bends through the
# reference's settings, `patch` through one of its four small functions.
BENT = {
    # the mean's bound four hundred times wider: its multiplier's gradient
    # changes sign on the updates where the KL lies between the two bounds
    "epsilon_mean_x400": (dict(epsilon_mean=1.0), None, None, "change.log_alpha"),
    # coupled MPO: both fits and both bounds on the online (mean, scale) pair
    "coupled_kl": ({}, "fixed_pairs", lambda mean, std, mean_t, std_t: ((mean, std), (mean, std)), "change.actor"),
    # action penalisation off: the value weights alone
    "no_penalty": ({}, "penalised", lambda w_value, w_penalty: w_value, "change.actor"),
    # Polyak with tau 0.05 where the copy belongs
    "polyak_for_the_copy": (
        {}, "moved_targets",
        lambda online, target, step, hp: jax.tree.map(lambda o, t: 0.05 * o + 0.95 * t, online, target),
        "change.target_critic",
    ),
    # one sample fewer: other draws, other weights
    "one_sample_fewer": (dict(samples=HP["samples"] - 1), None, None, "td0"),
    # D4PG's habit: one distribution from the mean of the samples' logits
    "mean_of_logits": (
        {}, "mixture_of", lambda logp: jax.nn.softmax(jnp.mean(logp, axis=0), axis=-1), "critic_loss",
    ),
    # the control: every product's operands rounded to float8_e5m2
    "float8_e5m2_products": ({}, None, None, "td0"),
}


@pytest.mark.parametrize("bend", sorted(BENT))
def test_a_bent_reference_fails_a_stated_number(dmpo, chunks, monkeypatch, bend):
    s0, ref0, s1, td, metrics, batches = chunks[("chunk", UPDATES)]
    sound = gaps(s0, s1, td, metrics, ref0, *follow(dmpo, ref0, batches))
    assert all(value <= tol for value, tol in sound.values())
    hp, name, bent_fn, number = BENT[bend]
    if name:
        monkeypatch.setattr(dmpo, name, bent_fn)
    operands = "float8_e5m2" if bend == "float8_e5m2_products" else None
    bent = gaps(s0, s1, td, metrics, ref0, *follow(dmpo, ref0, batches, {**HP, **hp}, operands, bend))
    assert bent[number][0] > 10 * bent[number][1], (bend, bent)


def test_the_single_step_and_the_chunk_draw_the_same(chunks):
    for a, b in zip(jax.tree.leaves(view(chunks[("step", UPDATES)][2])), jax.tree.leaves(view(chunks[("chunk", UPDATES)][2]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=2e-6)


def test_the_data_mesh_step_is_the_one_device_step(chunks):
    """8 virtual devices, the global batch unchanged: the same state to the
    order of the partitioner's reductions (the scale's multiplier apart: its
    gradient, epsilon_stddev - KL with both at 1e-6, changes sign with the
    order of a sum, and Adam makes a full step of either sign)."""
    one, mesh = view(chunks[("chunk", UPDATES)][2]), view(chunks[("mesh8", UPDATES)][2])
    one["log_alpha"] = {k: v for k, v in one["log_alpha"].items() if k != "log_alpha_stddev"}
    mesh["log_alpha"] = {k: v for k, v in mesh["log_alpha"].items() if k != "log_alpha_stddev"}
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(mesh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-4)


def test_replicas_hold_one_set_of_duals_under_the_explicit_data_axis(dmpo, storage, chunks):
    """Explicit mode (shard_map, per-step pmean): each replica draws its own
    rows' actions (the device's fold), so the state is another; what must
    hold is that every replica's multipliers saw the GLOBAL batch's KLs and
    its temperature the global mean: both replicas end on one set of duals,
    to the bit, and on one policy."""
    s0, _ = seeded(dmpo)
    learner = chunk_learner(jax.devices()[:2], UPDATES, mode="explicit")
    learner.state = jax.device_put(jax.tree.map(jnp.copy, s0), learner._state_sharding)
    end = learner.run_sample_chunk(Ring(storage)).state
    for leaf in jax.tree.leaves((end.log_alpha, end.actor_params)):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(shards) == 2
        np.testing.assert_array_equal(shards[0], shards[1])
    one = chunks[("chunk", UPDATES)][2]
    np.testing.assert_allclose(end.log_alpha["log_temperature"], one.log_alpha["log_temperature"], atol=2e-3)


def test_work_counts_what_the_issue_counts(dmpo):
    env = {"obs_dim": 376, "act_dim": 17}
    hp = {**HP, "actor_hidden": [256, 256, 256], "critic_hidden": [512, 512, 256], "batch_size": 256,
          "num_atoms": 51, "samples": 20}
    w = dmpo.work(env, hp)
    critic, policy = 393 * 512 + 512 * 512 + 512 * 256 + 256 * 51, 376 * 256 + 2 * 256 * 256 + 256 * 34
    assert (critic, policy) == (607_488, 236_032)  # ISSUE 51's two counts
    assert w["estep_flops"] == 2.0 * 256 * policy + 2.0 * 5120 * critic
    assert w["flops"] == w["estep_flops"] + 3 * 2.0 * 256 * (critic + policy)
    assert 7.6e9 < w["flops"] < 7.7e9 and 0.82 < w["estep_flops"] / w["flops"] < 0.84  # "7.64 GFLOP, 83% of it the E-step"
    assert w["row_bytes"] == 4.0 * 256 * (2 * 376 + 17 + 3)
    # and the count is the program's own state, value for value
    cfg = config(actor_hidden=(256, 256, 256), critic_hidden=(512, 512, 256), batch_size=256, num_atoms=51, mpo_samples=20)
    state = jax.eval_shape(lambda: init_train_state(cfg, 376, 17, 0))
    nets = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves((state.actor_params, state.critic_params)))
    duals = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(state.log_alpha))
    assert duals == 2 + 2 * 17
    assert w["state_bytes"] == 2.0 * 4 * (4 * nets + 3 * duals)
