"""TD3 against its plain reference (benchmarks/reference/td3.py), at a small
size on the CPU: the scan step and the interpreted megakernel follow the
reference's updates on seeded weights across a launch boundary that the
delay's phase crosses; the smoothing noise is one stream on both sides; the
records' counters say what the state says.

The reference is loaded from its one file under benchmarks/, by path, so
there is no second copy to drift.
"""

import importlib
import os
import sys

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
from distributed_ddpg_tpu.ops import fused_chunk
from distributed_ddpg_tpu.types import unpack_batch

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")

ENV = {"obs_dim": 17, "act_dim": 6, "action_scale": 1.0, "action_offset": 0.0}
HP = {
    "hidden": [40, 30], "gamma": 0.99, "tau": 0.005, "actor_lr": 1e-3, "critic_lr": 1e-3,
    "batch_size": 12,  # no multiple of the 8 sublanes, as the paper's 100 is none
    "policy_delay": 2, "target_noise": 0.2, "target_noise_clip": 0.5,
}
# An odd first step: the chunk's first update skips the actor, and the
# delay's phase (state.step % 2) is carried into the launch, not restarted.
UPDATES, STEP0, SEED = 9, 3, 11


@pytest.fixture(scope="module")
def td3():
    sys.path.insert(0, BENCH)
    try:
        return importlib.import_module("reference.td3")
    finally:
        sys.path.remove(BENCH)


def config(**kw):
    return DDPGConfig(
        twin_critic=True, policy_delay=HP["policy_delay"], target_noise=HP["target_noise"],
        target_noise_clip=HP["target_noise_clip"], actor_hidden=tuple(HP["hidden"]),
        critic_hidden=tuple(HP["hidden"]), batch_size=HP["batch_size"], actor_lr=HP["actor_lr"],
        critic_lr=HP["critic_lr"], tau=HP["tau"], seed=SEED, **kw,
    )


def rows(seed, n):
    """Packed rows [obs | action | R | d | next_obs | w], a few of them
    terminal, weights 1."""
    o, a = ENV["obs_dim"], ENV["act_dim"]
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    obs = jax.random.normal(k[0], (n, o))
    disc = HP["gamma"] * (jax.random.uniform(k[3], (n, 1)) > 0.05)
    return jnp.concatenate(
        [obs, jax.random.uniform(k[1], (n, a), minval=-1.0, maxval=1.0), jax.random.normal(k[2], (n, 1)), disc,
         obs + 0.1 * jax.random.normal(k[4], (n, o)), jnp.ones((n, 1))], axis=1,
    ).astype(jnp.float32)


def at_step(state, step0, counts):
    """`state` as a run that has made `step0` updates would carry its
    counters (the moments stay zero: both sides start from the same)."""
    actor_count, critic_count = (jnp.asarray(c, jnp.int32) for c in counts)
    return state._replace(
        step=jnp.asarray(step0, jnp.int32),
        actor_opt=state.actor_opt._replace(count=actor_count),
        critic_opt=state.critic_opt._replace(count=critic_count),
    )


def program_chunk(leg, s0, batches):
    """(state after, td [K, B], the chunk's metrics, per-update metrics or
    None where the leg reports only the chunk's) from the program's own
    updates on `batches` [K, B, width], starting from `s0`."""
    cfg = config(fused_chunk="on" if leg == "kernel" else "off")
    if leg == "kernel":
        run = fused_chunk.make_fused_chunk_fn(
            cfg, ENV["obs_dim"], ENV["act_dim"], ENV["action_scale"], ENV["action_offset"],
            chunk_size=batches.shape[0], interpret=True,
        )
        s1, td, metrics = jax.jit(run)(s0, batches)
        per_update = None
    else:
        step = make_learner_step(cfg, ENV["action_scale"], action_offset=ENV["action_offset"])

        def body(s, packed):
            out = step(s, unpack_batch(packed, ENV["obs_dim"], ENV["act_dim"]))
            return out.state, (out.td_errors, out.metrics)

        s1, (td, per_update) = jax.jit(lambda s, b: jax.lax.scan(body, s, b))(s0, batches)
        metrics = chunk_metrics(per_update)
    assert set(metrics) == set(metric_keys(cfg)) and "td3_twin_gap" in metrics
    return s1, td, metrics, per_update


def view(state):
    return {"actor": state.actor_params, "critic": state.critic_params,
            "target_actor": state.target_actor_params, "target_critic": state.target_critic_params}


def seeded(td3):
    """The program's and the reference's seeded states, moved to STEP0."""
    cfg = config()
    counts = (delayed_updates(STEP0, HP["policy_delay"]), STEP0)
    s0 = at_step(init_train_state(cfg, ENV["obs_dim"], ENV["act_dim"], SEED), STEP0, counts)
    ref0 = td3.init(SEED, ENV, HP)
    ref0["step"] = s0.step
    ref0["actor_opt"]["count"], ref0["critic_opt"]["count"] = s0.actor_opt.count, s0.critic_opt.count
    return s0, ref0


@pytest.mark.parametrize("leg", ["scan", "kernel"])
def test_program_follows_the_reference_across_an_odd_first_step(td3, leg):
    batches = rows(3, UPDATES * HP["batch_size"]).reshape(UPDATES, HP["batch_size"], -1)
    s0, ref0 = seeded(td3)
    s1, td, metrics, per_update = program_chunk(leg, s0, batches)
    ref1, ref = jax.jit(lambda s, b: jax.lax.scan(td3.make_step(SEED, ENV, HP), s, b))(ref0, batches)

    # Both sides are float32 on the CPU, so what is left is the order of
    # rounding: the reference multiplies at Precision.HIGHEST and takes
    # gradients by autodiff, the program uses XLA:CPU's default dot and, in
    # the kernel, a backward pass written out by hand.
    for k in view(s0):  # the seeded weights: the same keys, the same draws
        for a, b in zip(jax.tree.leaves(view(s0)[k]), jax.tree.leaves(ref0[k])):
            np.testing.assert_array_equal(a, b)
    # update 0's td, row by row: the forward pass of both critics, both
    # targets, the target policy, the smoothing noise and the minimum, on
    # returns of size 1: 1e-5 absolute is a hundred float32 epsilons. A
    # noise stream read at another step, or the mean of the two targets for
    # their minimum, moves it in the second digit.
    np.testing.assert_allclose(td[0], ref["td"][0], atol=1e-5, rtol=0)
    # every update's td: nine Adam steps of 1e-3 carry the rounding on
    np.testing.assert_allclose(td, ref["td"], atol=2e-4, rtol=0)
    # the losses: the critics' (a mean over 24 squared errors of size 1) to
    # 1e-4 relative, the actor's (-mean Q_1, a few hundredths) to 1e-5
    # absolute; update by update where the leg reports them so (the kernel
    # accumulates the chunk's mean inside the launch)
    assert float(metrics["critic_loss"]) == pytest.approx(float(jnp.mean(ref["critic_loss"])), rel=1e-4)
    assert float(metrics["actor_loss"]) == pytest.approx(float(jnp.mean(ref["actor_loss"])), abs=1e-5)
    # actor_grad_norm is 0 on a skipped update on both sides, so its mean
    # says how many updates took the actor's gradient: 4 of these 9
    assert float(metrics["actor_grad_norm"]) == pytest.approx(float(jnp.mean(ref["actor_grad_norm"])), rel=1e-3)
    assert int(jnp.sum(ref["actor_grad_norm"] > 0)) == 4
    if per_update is not None:
        np.testing.assert_allclose(per_update["critic_loss"], ref["critic_loss"], rtol=1e-4)
        np.testing.assert_allclose(per_update["actor_loss"], ref["actor_loss"], atol=1e-5, rtol=0)
        np.testing.assert_allclose(per_update["td3_twin_gap"], ref["twin_gap"], atol=1e-5, rtol=0)
        assert (np.asarray(per_update["actor_grad_norm"]) > 0).tolist() == (np.asarray(ref["actor_grad_norm"]) > 0).tolist()
    # the chunk reports its LAST update's twin gap, not the mean
    assert float(metrics["td3_twin_gap"]) == pytest.approx(float(ref["twin_gap"][-1]), abs=1e-5)
    assert abs(float(ref["twin_gap"][-1]) - float(jnp.mean(ref["twin_gap"]))) > 1e-4
    # every net's change over the chunk, leaf by leaf, to 1% of the leaf's
    # own change or of the net's median leaf's: Adam's first steps divide a
    # gradient by its own size, which turns a rounding in a near-zero
    # gradient into a visible share of one step. An actor moved on every
    # update would have gone twice as far; a target moved on every update
    # likewise.
    for k in view(s1):
        d_ref = [np.asarray(b1 - b0) for b1, b0 in zip(jax.tree.leaves(ref1[k]), jax.tree.leaves(ref0[k]))]
        d_prog = [np.asarray(a1 - a0) for a1, a0 in zip(jax.tree.leaves(view(s1)[k]), jax.tree.leaves(view(s0)[k]))]
        floor = np.median([np.linalg.norm(d) for d in d_ref])
        for dr, dp in zip(d_ref, d_prog):
            assert np.linalg.norm(dp - dr) <= 0.01 * max(np.linalg.norm(dr), floor), k
    # the counters: the actor's Adam count is the record's td3_actor_updates
    assert int(s1.step) == STEP0 + UPDATES == int(ref1["step"])
    assert int(s1.critic_opt.count) == STEP0 + UPDATES
    assert int(s1.actor_opt.count) == delayed_updates(STEP0 + UPDATES, HP["policy_delay"]) == int(ref1["actor_opt"]["count"])


@pytest.mark.parametrize("leg", ["scan", "kernel"])
def test_a_skipped_update_leaves_actor_and_targets_bit_for_bit(td3, leg):
    """One update from an odd step, with Adam moments that are not zero
    (the state after the chunk above): the critics move; the actor, its
    moments and count, and all three targets come back as the bits they
    were. Then one from an even step: all of them move."""
    batches = rows(5, (UPDATES + 2) * HP["batch_size"]).reshape(UPDATES + 2, HP["batch_size"], -1)
    s0, _ = seeded(td3)
    warm = program_chunk(leg, s0, batches[:UPDATES])[0]  # step 12: even
    moved = program_chunk(leg, warm, batches[UPDATES : UPDATES + 1])[0]  # update at step 12 moves them
    assert int(moved.step) == STEP0 + UPDATES + 1 and int(moved.step) % HP["policy_delay"] == 1
    skipped = program_chunk(leg, moved, batches[UPDATES + 1 :])[0]  # update at step 13 skips

    def frozen(s):
        return (s.actor_params, s.actor_opt.mu, s.actor_opt.nu, s.actor_opt.count,
                s.target_actor_params, s.target_critic_params)

    for a, b in zip(jax.tree.leaves(frozen(moved)), jax.tree.leaves(frozen(skipped))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(skipped.critic_params), jax.tree.leaves(moved.critic_params)):
        assert np.any(np.asarray(a) != np.asarray(b))
    assert int(skipped.critic_opt.count) == int(moved.critic_opt.count) + 1
    for a, b in zip(jax.tree.leaves(frozen(warm)), jax.tree.leaves(frozen(moved))):
        assert np.any(np.asarray(a) != np.asarray(b))


@pytest.mark.parametrize("seed,step0", [(0, 0), (11, 3), (2_147_483_659, 800)])
def test_the_reference_draws_the_programs_noise_stream(td3, seed, step0):
    """`td3.smoothing_noise` from the reference's key, update by update,
    against `learner.chunk_noise`, what both legs' chunks scan over: the
    same bits, for a seed past 2**31 too."""
    cfg = config().replace(seed=seed)
    b, a, k = HP["batch_size"], ENV["act_dim"], 5
    ours = chunk_noise(cfg, noise_base_key(cfg), jnp.asarray(step0, jnp.int32), k, b, a)
    key = td3.init(seed, ENV, HP)["noise_key"]
    theirs = jnp.stack([td3.smoothing_noise(key, jnp.asarray(step0 + i, jnp.int32), HP, (b, a)) for i in range(k)])
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


def test_the_noise_is_clipped_where_the_normal_tail_says(td3):
    """sigma 0.2 clipped at 0.5 is a normal cut at 2.5 sigma: 1.24% of the
    draws sit on the clip. 48,000 draws put the sampling error of that
    share at 0.05 points; a sigma of 0.1 or a clip at 1.0 reads under 0.01%."""
    cfg = config()
    eps = np.asarray(chunk_noise(cfg, noise_base_key(cfg), jnp.asarray(0, jnp.int32), 80, 100, ENV["act_dim"]))
    assert np.abs(eps).max() == pytest.approx(HP["target_noise_clip"])
    share = np.mean(np.abs(eps) == np.float32(HP["target_noise_clip"]))
    assert share == pytest.approx(0.0124, abs=0.0025)
    assert np.std(eps) == pytest.approx(0.2, abs=0.01)


def test_work_counts_the_algorithm_over_the_delays_period(td3):
    """Matmul operations of one update, averaged over the period: at delay 2
    the actor's three passes and critic 1's three under it count half."""
    hp = {**HP, "hidden": [400, 300], "batch_size": 100}
    # one critic, every pass on every update: what reference/ddpg.py counts
    plain = td3.c.work(ENV, hp, actor_out=ENV["act_dim"], n_critics=1, actor_passes=4.0, critic_passes=7.0)
    w = td3.work(ENV, hp)
    f_actor = 2.0 * 100 * (17 * 400 + 400 * 300 + 300 * 6)
    f_critic = 2.0 * 100 * (17 * 400 + 406 * 300 + 300 * 1)
    assert w["flops"] == pytest.approx(2.5 * f_actor + 9.5 * f_critic)
    assert w["row_bytes"] == plain["row_bytes"]
    one_critic = 17 * 400 + 400 + 406 * 300 + 300 + 300 + 1
    assert w["state_bytes"] - plain["state_bytes"] == 2 * 4 * 4 * one_critic
    every = td3.work(ENV, {**hp, "policy_delay": 1})
    assert every["flops"] == pytest.approx(4.0 * f_actor + 11.0 * f_critic)
