"""REDQ (arXiv 2101.05982) as a configuration of the SAC step: an ensemble of
N critics on the leading axis the twin pair had, a drawn in-target subset and
a delayed policy. What tests/test_reference_redq.py leaves: the gates, the
seeding, the subset's distribution, that plain SAC is untouched, the leg, the
checkpoint, and a run through train()."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.learner import (
    chunk_noise,
    delayed_updates,
    init_train_state,
    jit_learner_step,
    make_learner_step,
    metric_keys,
    noise_base_key,
    step_noise,
)
from distributed_ddpg_tpu.models.mlp import critic_init
from distributed_ddpg_tpu.ops import fused_chunk
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.types import Batch

OBS, ACT, B = 5, 2, 16

# Every way a sac configuration can leave the plain twin pair.
ENSEMBLES = {
    "the-papers": dict(critic_ensemble=10, target_subset=2, policy_delay=20),
    "minimum-over-all-five": dict(critic_ensemble=5, target_subset=5),
    "a-delayed-twin": dict(policy_delay=3),
    "one-of-two": dict(target_subset=1),
}


def _cfg(**kw):
    base = dict(actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=B, sac=True, seed=0)
    base.update(kw)
    return DDPGConfig(**base)


def _batch(rng):
    return Batch(
        obs=jnp.asarray(rng.standard_normal((B, OBS)), jnp.float32),
        action=jnp.asarray(rng.uniform(-1, 1, (B, ACT)), jnp.float32),
        reward=jnp.asarray(rng.standard_normal(B), jnp.float32),
        discount=jnp.full((B,), 0.99, jnp.float32),
        next_obs=jnp.asarray(rng.standard_normal((B, OBS)), jnp.float32),
        weight=jnp.ones((B,), jnp.float32),
    )


def test_config_gates():
    with pytest.raises(ValueError, match="target_subset"):
        DDPGConfig(sac=True, critic_ensemble=3, target_subset=4)
    with pytest.raises(ValueError, match="target_subset"):
        DDPGConfig(sac=True, target_subset=0)
    for family in (dict(), dict(twin_critic=True), dict(distributional=True)):
        with pytest.raises(ValueError, match="sac"):
            DDPGConfig(critic_ensemble=10, **family)
        with pytest.raises(ValueError, match="sac"):
            DDPGConfig(target_subset=1, **family)
    with pytest.raises(ValueError, match="policy_delay"):
        DDPGConfig(policy_delay=2)  # neither twin_critic nor sac
    DDPGConfig(sac=True, policy_delay=20)
    DDPGConfig(twin_critic=True, policy_delay=2)
    # plain sac, and twin_critic with its delay, are no ensemble runs
    assert not _cfg().redq and not _cfg(critic_ensemble=2, target_subset=2, policy_delay=1).redq
    assert not DDPGConfig(twin_critic=True, policy_delay=2).redq
    assert metric_keys(_cfg()) == metric_keys(DDPGConfig())
    flags = ["--sac=true", "--critic_ensemble=10", "--target_subset=2", "--policy_delay=20"]
    cfg = DDPGConfig.from_flags(flags)
    assert (cfg.critic_ensemble, cfg.target_subset, cfg.policy_delay, cfg.redq) == (10, 2, 20, True)


@pytest.mark.parametrize("name", sorted(ENSEMBLES))
def test_every_ensemble_configuration_takes_the_scan_leg(name):
    """`supported()` says no, so the learner picks the scan chunk by itself,
    forced-on kernel or not; `state_vmem_bytes` counts N critics."""
    cfg = _cfg(**ENSEMBLES[name])
    assert cfg.redq and not fused_chunk.supported(cfg)
    assert metric_keys(cfg)[-1] == "redq_q_spread"
    learner = ShardedLearner(cfg, OBS, ACT, 1.0, 0.0, chunk_size=2, mesh=mesh_lib.make_mesh(devices=jax.devices()[:1]))
    assert not learner.fused_chunk_active
    with pytest.raises(ValueError, match="envelope"):
        ShardedLearner(cfg.replace(fused_chunk="on"), OBS, ACT, 1.0, 0.0, chunk_size=2,
                       mesh=mesh_lib.make_mesh(devices=jax.devices()[:1]))
    one = (OBS * 32 + 32) + ((32 + ACT) * 32 + 32) + (32 + 1)
    twin = fused_chunk.state_vmem_bytes(_cfg(), OBS, ACT)
    assert fused_chunk.state_vmem_bytes(cfg, OBS, ACT) - twin == 4 * 4 * one * (cfg.critic_ensemble - 2)


def test_init_stacks_n_independently_seeded_critics():
    cfg = _cfg(critic_ensemble=10)
    s = init_train_state(cfg, OBS, ACT, seed=7)
    k_critic = jax.random.split(jax.random.PRNGKey(7))[1]
    for i, k in enumerate(jax.random.split(k_critic, 10)):
        member = critic_init(k, OBS, ACT, (32, 32), 1, 1)
        for got, want in zip(jax.tree.leaves(jax.tree.map(lambda x: x[i], s.critic_params)), jax.tree.leaves(member)):
            np.testing.assert_array_equal(got, want)
    w = np.asarray(s.critic_params[0]["w"])
    assert w.shape == (10, OBS, 32) and len({w[i].tobytes() for i in range(10)}) == 10
    assert s.critic_opt.mu[0]["w"].shape == s.target_critic_params[0]["w"].shape == (10, OBS, 32)
    # the twin pair's seeds are what they were: the first two of any ensemble
    # are NOT the pair (split(k, 10)[:2] != split(k, 2)), the pair itself is
    twin = init_train_state(_cfg(), OBS, ACT, seed=7)
    k1, k2 = jax.random.split(k_critic)
    for i, k in enumerate((k1, k2)):
        for got, want in zip(jax.tree.leaves(jax.tree.map(lambda x: x[i], twin.critic_params)),
                             jax.tree.leaves(critic_init(k, OBS, ACT, (32, 32), 1, 1))):
            np.testing.assert_array_equal(got, want)


def test_the_subset_is_without_replacement_and_uniform_over_the_45_pairs():
    """10,000 steps' draws at N = 10, M = 2, as one launch would draw them in
    front of its scan. Chi-square against the uniform law over the 45
    unordered pairs, 44 degrees of freedom: mean 44, standard deviation 9.4;
    the bound 85 is its 0.9998 quantile, and this seed's stream is fixed, so
    the test cannot flake. A draw with replacement, or one that favoured low
    indices by a tenth, reads in the hundreds."""
    cfg = _cfg(**ENSEMBLES["the-papers"])
    steps = 10_000
    subset = np.asarray(chunk_noise(cfg, noise_base_key(cfg), jnp.asarray(0, jnp.int32), steps, 1, ACT)[2])
    assert subset.shape == (steps, 2) and subset.dtype == np.int32
    assert subset.min() == 0 and subset.max() == 9
    assert np.all(subset[:, 0] != subset[:, 1])
    pairs = {p: 0 for p in itertools.combinations(range(10), 2)}
    for a, b in np.sort(subset, axis=1):
        pairs[(a, b)] += 1
    expected = steps / 45
    chi2 = sum((n - expected) ** 2 / expected for n in pairs.values())
    assert chi2 < 85.0, chi2
    # either order of a pair comes as often as the other
    first_lower = np.mean(subset[:, 0] < subset[:, 1])
    assert abs(first_lower - 0.5) < 0.02
    # M = 3 of 5: three distinct members every step
    cfg3 = _cfg(critic_ensemble=5, target_subset=3)
    three = np.asarray(chunk_noise(cfg3, noise_base_key(cfg3), jnp.asarray(0), 500, 1, ACT)[2])
    assert all(len(set(row)) == 3 for row in three.tolist())


def test_every_replica_draws_the_same_subset_and_its_own_normals():
    """Under shard_map each device folds its index into the key of the
    normals (its own rows of the global batch) and not into the subset's:
    the critics' gradient is averaged across replicas against one y a row."""
    cfg = _cfg(**ENSEMBLES["the-papers"])
    base = noise_base_key(cfg)
    plain = step_noise(cfg, base, jnp.asarray(17), B, ACT)
    folded = [step_noise(cfg, base, jnp.asarray(17), B, ACT, device_fold=jnp.asarray(d)) for d in (0, 1, 5)]
    for f in folded:
        np.testing.assert_array_equal(np.asarray(f[2]), np.asarray(plain[2]))
    assert not np.array_equal(np.asarray(folded[0][0]), np.asarray(folded[1][0]))
    # the normals of an ensemble run are plain sac's: the subset is a third
    # member, drawn beside them
    sac = step_noise(_cfg(), noise_base_key(_cfg()), jnp.asarray(17), B, ACT)
    assert len(sac) == 2 and len(plain) == 3
    for a, b in zip(sac, plain[:2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and on a real 2-device explicit-mode mesh the chunk runs with the set
    # replicated (P()) beside noise sharded like the batch
    mesh = mesh_lib.make_mesh(devices=jax.devices()[:2])
    small = _cfg(critic_ensemble=5, target_subset=2, policy_delay=3, batch_size=8)
    learner = ShardedLearner(small, OBS, ACT, 1.0, 0.0, chunk_size=4, mesh=mesh, mode="explicit")
    packed = jnp.asarray(np.random.default_rng(0).standard_normal((4, 16, 2 * OBS + ACT + 3)), jnp.float32)
    out = learner._chunk_step(learner.state, jax.device_put(packed, learner._chunk_sharding))
    assert np.isfinite(float(out.metrics["critic_loss"])) and int(out.state.actor_opt.count) == 2
    for leaf in jax.tree.leaves(out.state.critic_params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        np.testing.assert_array_equal(shards[0], shards[1])  # replicas did not fork


def test_n_2_m_2_delay_1_is_todays_sac_step_program_for_program():
    """The three fields at their defaults, spelt out or not, are one
    configuration and one lowered program; and the delayed step's taken
    branch is the plain step's arithmetic: from step 0 a delayed twin moves
    every net, the temperature and every Adam moment to the plain step's
    bits, and from step 1 hands actor and temperature on untouched while the
    critics and their targets move as the plain step moves them."""
    plain, spelt = _cfg(), _cfg(critic_ensemble=2, target_subset=2, policy_delay=1)
    assert plain == spelt
    s = init_train_state(plain, OBS, ACT, seed=0)
    batch = _batch(np.random.default_rng(4))
    texts = [jax.jit(make_learner_step(c, 1.0)).lower(s, batch).as_text() for c in (plain, spelt)]
    assert texts[0] == texts[1] and "stablehlo.case" not in texts[0] and "stablehlo.if" not in texts[0]
    delayed = _cfg(policy_delay=3)
    step_plain = jit_learner_step(plain, 1.0, donate=False)
    step_delayed = jit_learner_step(delayed, 1.0, donate=False)
    a, b = step_plain(s, batch), step_delayed(s, batch)  # step 0: the policy steps
    for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(a.td_errors), np.asarray(b.td_errors))
    for k in ("critic_loss", "actor_loss", "actor_grad_norm", "critic_grad_norm", "td_abs_mean"):
        assert float(a.metrics[k]) == float(b.metrics[k]), k
    a2, b2 = step_plain(a.state, batch), step_delayed(b.state, batch)  # step 1: it does not
    for name in ("actor_params", "actor_opt", "log_alpha", "alpha_opt"):
        for x, y in zip(jax.tree.leaves(getattr(b2.state, name)), jax.tree.leaves(getattr(b.state, name))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for name in ("critic_params", "critic_opt", "target_critic_params"):
        for x, y in zip(jax.tree.leaves(getattr(b2.state, name)), jax.tree.leaves(getattr(a2.state, name))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert float(b2.metrics["actor_loss"]) == 0.0 == float(b2.metrics["actor_grad_norm"])
    assert int(b2.state.actor_opt.count) == int(b2.state.alpha_opt.count) == 1 and int(b2.state.critic_opt.count) == 2


def test_target_takes_the_drawn_pair_and_the_actor_the_mean():
    """Critics offset by constants, as tests/test_sac.py's minimum test has
    them: with members at +0, +100, ..., +400 the target is the lower of the
    drawn pair's offsets, and mean_q the mean offset; redq_q_spread is their
    standard deviation."""
    cfg = _cfg(critic_ensemble=5, target_subset=2, sac_autotune=False)
    s = init_train_state(cfg, OBS, ACT, seed=0)
    offsets = jnp.arange(5, dtype=jnp.float32) * 100.0

    def shifted(critic):
        last = {"w": critic[-1]["w"] * 0.0, "b": critic[-1]["b"] * 0.0 + offsets[:, None]}
        return (*critic[:-1], last)

    s = s._replace(critic_params=shifted(s.critic_params), target_critic_params=shifted(s.target_critic_params))
    batch = _batch(np.random.default_rng(1))._replace(reward=jnp.zeros((B,)), discount=jnp.ones((B,)))
    step = jax.jit(make_learner_step(cfg, 1.0))
    base = noise_base_key(cfg)
    seen = set()
    for t in range(6):
        st = s._replace(step=jnp.asarray(t, jnp.int32))
        out = step(st, batch)
        subset = np.asarray(step_noise(cfg, base, jnp.asarray(t), B, ACT)[2])
        seen.add(tuple(sorted(subset.tolist())))
        # y = min over the pair - alpha * log pi; td = y - mean offset, so the
        # pair's lower offset is read off the td's level across steps
        lp_free = np.asarray(out.td_errors) + 200.0  # + mean offset
        level = float(min(offsets[subset]))
        assert np.all(np.abs(lp_free - level) < 40.0), (t, subset, lp_free[:3])  # |alpha * log pi| < 40
        assert float(out.metrics["mean_q"]) == pytest.approx(200.0, abs=1e-3)
        assert float(out.metrics["redq_q_spread"]) == pytest.approx(float(np.std(np.arange(5) * 100.0)), rel=1e-5)
    assert len(seen) > 2  # the set changes from update to update


def test_counts_follow_the_one_rule_across_launches():
    """Three launches of 7 updates at G = 3 on a data mesh of two: the
    actor's and the temperature's Adam counts are delayed_updates(steps, G),
    the record's `redq_policy_updates`, whatever phase a launch starts in."""
    cfg = _cfg(critic_ensemble=5, target_subset=2, policy_delay=3, batch_size=8)
    learner = ShardedLearner(cfg, OBS, ACT, 1.0, 0.0, chunk_size=7, mesh=mesh_lib.make_mesh(devices=jax.devices()[:2]))
    rng = np.random.default_rng(2)
    for launch in range(1, 4):
        packed = jnp.asarray(rng.standard_normal((7, learner.global_batch, 2 * OBS + ACT + 3)), jnp.float32)
        out = learner._chunk_step(learner.state, jax.device_put(packed, learner._chunk_sharding))
        learner.state = out.state
        steps = 7 * launch
        assert int(out.state.step) == int(out.state.critic_opt.count) == steps
        assert int(out.state.actor_opt.count) == int(out.state.alpha_opt.count) == delayed_updates(steps, 3)
    assert delayed_updates(21, 3) == 7


def test_checkpoint_round_trip_and_restore_under_another_mesh_at_n_10(tmp_path):
    """The state of a ten-critic run, moved off its seed by a few updates:
    saved from a data mesh of 8, restored, and placed under a (4, 2) mesh
    with tensor parallelism: the partition rules' trailing-dim alignment
    replicates the ensemble axis and shards the hidden dims as for one
    critic; the bits are the saved ones; and the chunk runs from there."""
    from distributed_ddpg_tpu import checkpoint as ckpt_lib

    cfg = _cfg(**ENSEMBLES["the-papers"], batch_size=8)
    state = init_train_state(cfg, OBS, ACT, seed=0)
    step = jit_learner_step(cfg, 1.0, donate=False)
    batch = jax.tree.map(lambda x: x[:8], _batch(np.random.default_rng(4)))
    for _ in range(3):
        state = step(state, batch).state
    mesh1 = mesh_lib.make_mesh(8, 1)
    placed = jax.device_put(state, mesh_lib.to_named(mesh1, mesh_lib.state_pspec(state, mesh1)))
    ckpt_lib.save(str(tmp_path / "a"), 3, placed, config=cfg)
    template = init_train_state(cfg, OBS, ACT, seed=1)
    restored, at, _ = ckpt_lib.restore(str(tmp_path / "a"), template, config=cfg)
    assert at == 3 and restored.critic_params[0]["w"].shape == (10, OBS, 32)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(jax.device_get(state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mesh2 = mesh_lib.make_mesh(4, 2)
    learner = ShardedLearner(cfg.replace(model_axis=2), OBS, ACT, 1.0, 0.0, chunk_size=2, mesh=mesh2)
    learner.state = jax.device_put(restored, learner._state_sharding)
    assert learner.state.critic_params[0]["w"].sharding.spec == P(None, None, "model")
    assert learner.state.critic_opt.mu[1]["w"].sharding.spec == P(None, "model", None)
    packed = jnp.asarray(
        np.random.default_rng(5).standard_normal((2, learner.global_batch, 2 * OBS + ACT + 3)), jnp.float32)
    out = learner._chunk_step(learner.state, jax.device_put(packed, learner._chunk_sharding))
    assert int(out.state.step) == 5 and np.isfinite(float(out.metrics["redq_q_spread"]))


def test_train_runs_redq_end_to_end_and_its_records_say_so(tmp_path):
    """The normal path at a small size: host actors, the device ring,
    run_sample_chunk on the scan leg, refresh, a checkpoint, and the records'
    ensemble keys; a plain sac run's records have none of them."""
    import json

    from distributed_ddpg_tpu.train import train

    def run(name, *extra):
        log = tmp_path / f"{name}.jsonl"
        cfg = DDPGConfig.from_flags([
            "--backend=jax_tpu", "--env_id=Pendulum-v1", "--sac=true", "--num_actors=2",
            "--total_env_steps=1500", "--replay_min_size=300", "--eval_every=0", "--actor_hidden=16,16",
            "--critic_hidden=16,16", "--replay_capacity=4096", "--batch_size=16", "--learner_chunk=10",
            "--max_ingest_ratio=2", f"--log_path={log}", f"--checkpoint_dir={tmp_path / name}",
            "--checkpoint_every=200", *extra,
        ])
        summary = train(cfg)
        return summary, [json.loads(line) for line in open(log)]

    summary, records = run("redq", "--critic_ensemble=5", "--target_subset=2", "--policy_delay=4")
    assert summary["fused_chunk_active"] is False
    assert (summary["critic_ensemble"], summary["target_subset"]) == (5, 2)
    assert summary["learner_steps"] >= 400
    assert summary["redq_policy_updates"] == delayed_updates(summary["learner_steps"], 4)
    header = next(r for r in records if r["kind"] == "header")
    final = next(r for r in records if r["kind"] == "final")
    assert header["critic_ensemble"] == final["critic_ensemble"] == 5 and final["target_subset"] == 2
    assert final["redq_policy_updates"] == summary["redq_policy_updates"]
    assert np.isfinite(final["redq_q_spread"]) and final["redq_q_spread"] > 0
    assert summary["param_checksum"] != summary["param_checksum_start"]
    assert any((tmp_path / "redq").iterdir())  # a checkpoint was written
    # and a second run resumes from it: the five critics, their
    # moments and the policy's own counts come back through restore
    resumed, _ = run("redq", "--critic_ensemble=5", "--target_subset=2", "--policy_delay=4",
                     "--total_env_steps=2000")
    assert resumed["learner_steps"] > summary["learner_steps"]
    plain, plain_records = run("sac")
    for r in plain_records + [plain]:
        assert not {"redq_q_spread", "redq_policy_updates", "critic_ensemble", "target_subset"} & set(r)
