"""SimBa against its plain reference (benchmarks/reference/simba.py), at a
small size on the CPU: the forward passes on seeded weights, the single
step, the scan chunk and the chunk on the 8-device data mesh follow the
reference's updates over 1 and over 8 updates; references bent on purpose
(no decay, rates 20% low, the targets on stale statistics, float8 products)
fail a stated number that the sound one passes; the input statistics after k
batches are the moments of the concatenated rows.

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
    SIMBA_KEYS,
    chunk_metrics,
    init_train_state,
    make_learner_step,
    metric_keys,
)
from distributed_ddpg_tpu.models import mlp
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.types import unpack_batch

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")

ENV = {"obs_dim": 11, "act_dim": 3, "action_scale": 0.4, "action_offset": 0.0}
# The source's rates and decay are 1e-4 and 1e-2: eight such updates move a
# weight by under a thousandth and the decay's share of that is a hundredth
# of a percent. Ten times the rates and the decay make both visible in 8.
HP = {
    "critic_hidden": [32, 32], "actor_hidden": [16], "gamma": 0.99, "tau": 0.005, "actor_lr": 1e-3,
    "critic_lr": 1e-3, "weight_decay": 0.1, "batch_size": 16, "alpha0": 0.01, "target_entropy_scale": 0.5,
}
UPDATES, SEED, ROWS, FINAL_SCALE = 8, 11, 64, 200.0


@pytest.fixture(scope="module")
def simba():
    sys.path.insert(0, BENCH)
    try:
        return importlib.import_module("reference.simba")
    finally:
        sys.path.remove(BENCH)


def config(**kw):
    base = dict(
        sac=True, simba=True, action_insert_layer=0, actor_hidden=tuple(HP["actor_hidden"]),
        critic_hidden=tuple(HP["critic_hidden"]), batch_size=HP["batch_size"], actor_lr=HP["actor_lr"],
        critic_lr=HP["critic_lr"], weight_decay=HP["weight_decay"], tau=HP["tau"], sac_alpha=HP["alpha0"],
        target_entropy_scale=HP["target_entropy_scale"], seed=SEED, scale_batch_with_data=False,
    )
    base.update(kw)
    return DDPGConfig(**base)


def rows(seed, n):
    """Packed rows [obs | action | R | d | next_obs | w], a few terminal,
    weights 1; the observation's columns on scales from a tenth to ten and
    off zero, so that the input normaliser has something to do."""
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
    return {"actor": state.actor_params, "critic": state.critic_params,
            "target_critic": state.target_critic_params, "log_alpha": state.log_alpha}


def seeded(simba):
    """The program's and the reference's seeded states: equal to the last
    bit, leaf for leaf. Seeded heads are U(+-3e-3), so both critics read a
    few thousandths a ten-thousandth apart and nothing a tolerance can hold
    would tell which the minimum took: both sides' heads are scaled alike."""
    s0 = init_train_state(config(), ENV["obs_dim"], ENV["act_dim"], SEED)
    ref0 = simba.init(SEED, ENV, HP)
    for k in view(s0):
        assert jax.tree.structure(view(s0)[k]) == jax.tree.structure(ref0[k])
        for a, b in zip(jax.tree.leaves(view(s0)[k]), jax.tree.leaves(ref0[k])):
            np.testing.assert_array_equal(a, b)
    wide = lambda critic: (*critic[:-1], {**critic[-1], **{k: FINAL_SCALE * critic[-1][k] for k in ("w", "b")}})
    s0 = s0._replace(critic_params=wide(s0.critic_params), target_critic_params=wide(s0.target_critic_params))
    ref0["critic"] = ref0["target_critic"] = wide(ref0["critic"])
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


def program_chunk(path, s0, storage, updates, **kw):
    """(state after, td [K, B], the chunk's metrics, rows [K, B, width]) of
    the program's own K updates from `s0`: `step` the jitted single step K
    times, `chunk` ShardedLearner's sampling chunk on one device (what
    `train()` launches), `mesh8` the same on the 8 virtual devices' data
    mesh at the same global batch."""
    sub = jax.random.split(jax.random.PRNGKey(SEED))[1]
    batches = storage[jax.random.randint(sub, (updates, HP["batch_size"]), 0, storage.shape[0])]
    if path == "step":
        step = jax.jit(make_learner_step(config(**kw), ENV["action_scale"], action_offset=ENV["action_offset"]))
        s, tds, ms = s0, [], []
        for k in range(updates):
            out = step(s, unpack_batch(batches[k], ENV["obs_dim"], ENV["act_dim"]))
            s = out.state
            tds.append(out.td_errors)
            ms.append(out.metrics)
        return s, jnp.stack(tds), chunk_metrics({k: jnp.stack([m[k] for m in ms]) for k in ms[0]}), batches
    learner = chunk_learner(jax.devices()[: 8 if path == "mesh8" else 1], updates, **kw)
    learner.state = jax.device_put(jax.tree.map(jnp.copy, s0), learner._state_sharding)  # the chunk donates
    out = learner.run_sample_chunk(Ring(storage))
    return out.state, out.td_errors, out.metrics, batches


def follow(simba, ref0, batches, hp=HP, operand_dtype=None):
    step = simba.make_step(SEED, ENV, hp, operand_dtype)
    return jax.jit(lambda s, b: jax.lax.scan(step, s, b))(ref0, batches)


def gaps(s0, s1, td, metrics, ref0, ref1, ref):
    """The numbers the comparison is made on, as {name: (value, tolerance)}.
    Both sides are float32 on the CPU, so what is left between a sound
    program and the reference is the order of rounding (the reference
    multiplies at Precision.HIGHEST and divides by a root where the program
    multiplies by a reciprocal root)."""
    out = {
        # update 0's td, row by row: the forward pass of actor, critics and
        # targets on seeded weights, on returns of size 1
        "td0": (float(jnp.max(jnp.abs(td[0] - ref["td"][0]))), 2e-5),
        # every update's td: AdamW steps of 1e-3 carry the rounding on
        "td": (float(jnp.max(jnp.abs(td - ref["td"]))), 5e-4),
        "critic_loss": (abs(float(metrics["critic_loss"]) / float(jnp.mean(ref["critic_loss"])) - 1.0), 1e-3),
        "actor_loss": (abs(float(metrics["actor_loss"]) - float(jnp.mean(ref["actor_loss"]))), 1e-4),
        # the chunk's LAST update's, and an exact count of rows
        "resid_share": (abs(float(metrics["resid_share"]) - float(ref["resid_share"][-1])), 1e-5),
        "rsnorm_drift": (abs(float(metrics["rsnorm_drift"]) - float(ref["rsnorm_drift"][-1])), 1e-5),
        "rsnorm_count": (abs(float(metrics["rsnorm_count"]) - float(ref["rsnorm_count"][-1])), 0.0),
    }
    # every net's change over the chunk, leaf by leaf (the statistics and
    # their count are leaves like the weights), to 1% of the leaf's own
    # change or of the net's median leaf's
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
def chunks(simba, storage):
    """Each path's run, made once for the comparisons below."""
    s0, ref0 = seeded(simba)
    runs = {(path, UPDATES): (s0, ref0, *program_chunk(path, s0, storage, UPDATES)) for path in ("step", "chunk", "mesh8")}
    runs[("chunk", 1)] = (s0, ref0, *program_chunk("chunk", s0, storage, 1))
    return runs


def test_forward_passes_on_seeded_weights(simba, storage):
    """Actor and critic against the reference's own forward, statistics off
    their identity values: to 1e-5 of outputs of size 1 (float32 on both
    sides; the orders of summation differ)."""
    s0, ref0 = seeded(simba)
    b = unpack_batch(storage[:32], ENV["obs_dim"], ENV["act_dim"])
    stats = (jnp.mean(storage[:, : ENV["obs_dim"]], 0), jnp.var(storage[:, : ENV["obs_dim"]], 0), jnp.asarray(64.0))
    actor, critic = mlp.rs_written(s0.actor_params, stats), mlp.rs_written(s0.critic_params, stats)
    mean, log_std = mlp.actor_gaussian_apply(actor, b.obs, -5.0, 2.0)
    q = jax.vmap(lambda p: mlp.critic_apply(p, b.obs, b.action, 0))(critic)
    # the reference's step at update 0 reads the same nets: its td is y - q
    ref_state = {**ref0, "actor": actor, "critic": critic, "target_critic": critic}
    _, m = simba.make_step(SEED, ENV, HP)(ref_state, storage[:32])
    out = make_learner_step(config(batch_size=32), ENV["action_scale"])(
        s0._replace(actor_params=actor, critic_params=critic, target_critic_params=critic), b)
    np.testing.assert_allclose(out.td_errors, m["td"], atol=1e-5, rtol=0)
    assert q.shape == (2, 32) and mean.shape == log_std.shape == (32, ENV["act_dim"])
    assert float(jnp.max(jnp.abs(q))) > 0.05  # the scaled heads read something


@pytest.mark.parametrize("path,updates", [("step", UPDATES), ("chunk", 1), ("chunk", UPDATES), ("mesh8", UPDATES)])
def test_program_follows_the_reference(simba, chunks, path, updates):
    s0, ref0, s1, td, metrics, batches = chunks[(path, updates)]
    ref1, ref = follow(simba, ref0, batches)
    assert set(metrics) == set(metric_keys(config())) and set(SIMBA_KEYS) <= set(metrics) and set(SIMBA_KEYS) <= set(LAST_UPDATE_KEYS)
    for name, (value, tol) in gaps(s0, s1, td, metrics, ref0, ref1, ref).items():
        assert value <= tol, (name, value, tol)
    assert int(s1.step) == updates == int(ref1["step"])
    assert float(metrics["rsnorm_count"]) == updates * HP["batch_size"]
    # one normaliser: actor's, critics' and targets' copies are equal to the
    # bit, the critics' two rows too
    a0 = s1.actor_params[0]
    for name in mlp.RS_STATS:
        for tree in (s1.critic_params, s1.target_critic_params, s1.target_actor_params):
            leaf = np.asarray(tree[0][name])
            np.testing.assert_array_equal(np.broadcast_to(np.asarray(a0[name]), leaf.shape), leaf)
    # the optimiser never had a say in a statistic: its moments there are zero
    for opt in (s1.actor_opt, s1.critic_opt):
        for name in mlp.RS_STATS:
            assert not np.any(np.asarray(opt.mu[0][name])) and not np.any(np.asarray(opt.nu[0][name]))


def test_statistics_after_k_batches_are_the_moments_of_the_concatenated_rows(chunks):
    _, _, s1, _, _, batches = chunks[("chunk", UPDATES)]
    seen = np.asarray(batches[..., : ENV["obs_dim"]], np.float64).reshape(-1, ENV["obs_dim"])
    a0 = s1.actor_params[0]
    assert float(a0["rs_count"]) == seen.shape[0] == UPDATES * HP["batch_size"]
    np.testing.assert_allclose(a0["rs_mean"], seen.mean(0), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(a0["rs_var"], seen.var(0), rtol=1e-4)


def test_target_critics_read_the_online_statistics(simba, chunks, monkeypatch):
    """A reference whose targets normalise with the statistics of the update
    BEFORE (what averaging them with tau, or forgetting to copy them, would
    leave near) parts from the program at once."""
    s0, ref0, s1, td, metrics, batches = chunks[("chunk", UPDATES)]
    stale = {k: ref0["critic"][0][k] for k in simba.STATS}
    monkeypatch.setattr(simba, "statistics_for_targets", lambda s: stale)
    bent = gaps(s0, s1, td, metrics, ref0, *follow(simba, ref0, batches))
    assert bent["td"][0] > 100 * bent["td"][1], bent["td"]


# What each fault moves, by a stated number: the sound program reads under
# the tolerance, the bent reference over ten times it.
BENT = {
    # the program decays, this reference does not: LayerNorm's scales, which
    # start at 1, shrink by rate * decay = 1e-4 of themselves an update
    "weight_decay_0": (dict(weight_decay=0.0), None, "change.critic"),
    # rates 20% low: every leaf's change is a fifth short
    "rates_20pct_low": (dict(actor_lr=0.8e-3, critic_lr=0.8e-3), None, "change.critic"),
    # the control: every product's operands rounded to float8_e5m2, the
    # normalisers' statistics and divisions left in float32
    "float8_e5m2_products": ({}, "float8_e5m2", "td0"),
}


@pytest.mark.parametrize("bend", sorted(BENT))
def test_a_bent_reference_fails_a_stated_number(simba, chunks, bend):
    s0, ref0, s1, td, metrics, batches = chunks[("chunk", UPDATES)]
    sound = gaps(s0, s1, td, metrics, ref0, *follow(simba, ref0, batches))
    assert all(value <= tol for value, tol in sound.values())
    hp, operands, number = BENT[bend]
    bent = gaps(s0, s1, td, metrics, ref0, *follow(simba, ref0, batches, {**HP, **hp}, operands))
    assert bent[number][0] > 10 * bent[number][1], (bend, bent)


def test_the_single_step_and_the_chunk_draw_the_same(chunks):
    for a, b in zip(jax.tree.leaves(view(chunks[("step", UPDATES)][2])), jax.tree.leaves(view(chunks[("chunk", UPDATES)][2]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=2e-6)


def test_the_data_mesh_step_is_the_one_device_step(chunks):
    """8 virtual devices, the global batch unchanged: the same state to the
    order of the partitioner's reductions (a batch mean is a sum of eight
    partial sums; eight AdamW steps of 1e-3 divide a gradient by its own
    root, so a weight whose gradient is near zero shows it at 2e-5), the
    statistics with it."""
    one, mesh = chunks[("chunk", UPDATES)][2], chunks[("mesh8", UPDATES)][2]
    for a, b in zip(jax.tree.leaves(view(one)), jax.tree.leaves(view(mesh))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-4)


def test_replicas_statistics_are_the_global_batchs_under_the_explicit_data_axis(simba, storage, chunks):
    """Explicit mode (shard_map, per-step pmean): each replica merges the
    GLOBAL batch's moments and counts its rows (two pmeans and the axis's
    size), so every replica holds the one-device chunk's statistics."""
    s0, _ = seeded(simba)
    learner = chunk_learner(jax.devices()[:2], UPDATES, mode="explicit")
    learner.state = jax.device_put(jax.tree.map(jnp.copy, s0), learner._state_sharding)
    end = learner.run_sample_chunk(Ring(storage)).state
    one = chunks[("chunk", UPDATES)][2]
    for name in mlp.RS_STATS:
        leaf = end.critic_params[0][name]
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(shards) == 2
        np.testing.assert_array_equal(shards[0], shards[1])
        np.testing.assert_allclose(leaf, one.critic_params[0][name], rtol=2e-5, atol=1e-6)


def test_work_counts_what_the_issue_counts(simba):
    env = {"obs_dim": 376, "act_dim": 17}
    hp = {**HP, "critic_hidden": [512, 512], "actor_hidden": [128], "batch_size": 256}
    w = simba.work(env, hp)
    s_c = 393 * 512 + 2 * (512 * 2048 + 2048 * 512) + 512
    s_a = 376 * 128 + 128 * 512 + 512 * 128 + 128 * 34
    assert w["flops"] == 2.0 * 256 * (4 * s_a + 14 * s_c)
    assert 26e9 < w["flops"] < 32e9  # ISSUE 44: "about 27 GFLOP" (it counts the actor's passes through the critics' first layer apart)
    assert w["row_bytes"] == 4.0 * 256 * (2 * 376 + 17 + 3)
    # and the count is the program's own state, value for value
    cfg = config(critic_hidden=(512, 512), actor_hidden=(128,), batch_size=256)
    state = jax.eval_shape(lambda: init_train_state(cfg, 376, 17, 0))
    online = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves((state.actor_params, state.critic_params)))
    target = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(state.target_critic_params))
    assert w["state_bytes"] == 2.0 * 4 * (3 * online + target)
    one_critic = simba.net_values(376, 393, 1, [512, 512])
    assert one_critic == 4_404_737 and simba.net_values(376, 376, 34, [128]) == 184_866  # ISSUE 44: 4.40 M and 0.185 M
