"""CrossQ (arXiv 1902.05605) as a configuration of the SAC step: no target
networks, batch-normalised nets, the joint critic pass. What
tests/test_reference_crossq.py leaves: the gates, the leg, the state's
shape, that every other family's program is untouched, the policy that
leaves the learner (the fold), the partition rules, the checkpoint, and a
run through train()."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.actors.policy import NumpyPolicy, actor_head_dim, flatten_params, param_layout
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.learner import (
    delayed_updates,
    init_train_state,
    jit_learner_step,
    make_act_fn,
    make_learner_step,
    metric_keys,
)
from distributed_ddpg_tpu.models import mlp
from distributed_ddpg_tpu.ops import fused_chunk
from distributed_ddpg_tpu.ops.optim import adam_update
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.types import Batch, OptState

OBS, ACT, B = 5, 2, 16
SOURCE = dict(crossq=True, policy_delay=3, adam_b1=0.5, action_insert_layer=0, actor_lr=1e-3, critic_lr=1e-3)


def _cfg(**kw):
    base = dict(actor_hidden=(16, 16), critic_hidden=(32, 32), batch_size=B, sac=True, seed=0)
    base.update(kw)
    return DDPGConfig(**base)


def _batch(rng, b=B):
    return Batch(
        obs=jnp.asarray(3.0 + 2.0 * rng.standard_normal((b, OBS)), jnp.float32),
        action=jnp.asarray(rng.uniform(-1, 1, (b, ACT)), jnp.float32),
        reward=jnp.asarray(rng.standard_normal(b), jnp.float32),
        discount=jnp.full((b,), 0.99, jnp.float32),
        next_obs=jnp.asarray(3.0 + 2.0 * rng.standard_normal((b, OBS)), jnp.float32),
        weight=jnp.ones((b,), jnp.float32),
    )


def _moved(cfg, updates=4, seed=4):
    """A state a few updates off its seed: statistics, scales and shifts have
    all left their identity values."""
    state = init_train_state(cfg, OBS, ACT, seed=0)
    step = jit_learner_step(cfg, 1.0, donate=False)
    rng = np.random.default_rng(seed)
    for _ in range(updates):
        state = step(state, _batch(rng, cfg.batch_size)).state
    return state


REFUSED = {
    "without-sac": (dict(sac=False, crossq=True), "set sac=True"),
    "on-the-native-backend": (dict(crossq=True, backend="native"), "crossq requires a JAX backend"),
    "with-an-ensemble": (dict(crossq=True, critic_ensemble=5, target_subset=2), "no target critics to draw"),
    "adam-b1-on-the-native-backend": (dict(sac=False, adam_b1=0.5, backend="native"), "hold 0.9 as a constant"),
    "adam-b1-out-of-range": (dict(adam_b1=1.0), r"adam_b1 must be in \[0, 1\)"),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_config_refuses_with_a_message(name):
    kw, message = REFUSED[name]
    with pytest.raises(ValueError, match=message):
        _cfg(**kw)


def test_each_predicate_says_what_it_names():
    """`crossq` with the paper's delay is no `redq` run (no ensemble keys in
    its records), a delayed sac without it still is, and the flags parse."""
    assert _cfg(**SOURCE).crossq and not _cfg(**SOURCE).redq
    assert _cfg(policy_delay=3).redq and not _cfg(policy_delay=3).crossq
    assert not _cfg().redq and not _cfg().crossq and _cfg().adam_b1 == 0.9
    cfg = DDPGConfig.from_flags(
        "--sac=true --crossq=true --critic_hidden=2048,2048 --adam_b1=0.5 --policy_delay=3 "
        "--action_insert_layer=0".split())
    assert (cfg.crossq, cfg.adam_b1, cfg.policy_delay, cfg.critic_hidden) == (True, 0.5, 3, (2048, 2048))
    assert metric_keys(cfg)[-1] == "bn_stat_gap" and "redq_q_spread" not in metric_keys(cfg)


@pytest.mark.parametrize("kw", [SOURCE, dict(crossq=True), dict(adam_b1=0.5)], ids=["source", "no-delay", "b1-alone"])
def test_the_kernel_is_not_supported_and_the_learner_takes_the_scan_leg(kw):
    """CrossQ has no kernel branch, and the kernel's Adam holds beta_1 = 0.9
    as a constant: `supported()` says no and the learner picks the scan leg
    by itself, also where the kernel is asked for by name."""
    cfg = _cfg(**kw)
    assert not fused_chunk.supported(cfg)
    assert fused_chunk.supported(_cfg())  # plain sac at this size still is
    learner = ShardedLearner(cfg.replace(fused_chunk="auto"), OBS, ACT, 1.0, 0.0, chunk_size=4)
    assert not learner.fused_chunk_active


def test_the_state_has_no_targets_and_the_step_traces_no_target_update():
    cfg = _cfg(**SOURCE)
    state = init_train_state(cfg, OBS, ACT, seed=0)
    assert state.target_actor_params is None and state.target_critic_params is None
    assert set(state.actor_params[0]) == {"w", "b", "bn_scale", "bn_shift", "bn_mean", "bn_var"}
    # BN_0 runs over the critic's whole input, the action with it; twin critics
    assert state.critic_params[0]["bn_mean"].shape == (2, OBS + ACT)
    assert state.critic_params[2]["bn_var"].shape == (2, 32) and state.critic_params[2]["w"].shape == (2, 32, 1)
    # plain sac, the same seed: the same weights, and targets
    plain = init_train_state(_cfg(action_insert_layer=0), OBS, ACT, seed=0)
    np.testing.assert_array_equal(plain.critic_params[1]["w"], state.critic_params[1]["w"])
    assert plain.target_critic_params is not None
    batch = _batch(np.random.default_rng(0))
    text = jax.jit(make_learner_step(cfg, 1.0)).lower(state, batch).as_text(debug_info=True)
    assert "polyak/" not in text and "critic/norm/" in text and "actor/norm/" in text
    sac = jax.jit(make_learner_step(_cfg(action_insert_layer=0), 1.0)).lower(plain, batch)
    assert "polyak/" in sac.as_text(debug_info=True)
    out = jit_learner_step(cfg, 1.0, donate=False)(state, batch)
    assert out.state.target_actor_params is None and out.state.target_critic_params is None
    assert jax.tree.structure(out.state) == jax.tree.structure(state)


def test_the_scope_vocabulary_has_norm_under_both_nets():
    assert {"update/critic/norm", "update/actor/norm"} <= set(trace.CHUNK_SCOPES)
    with trace.device_scope("norm"):
        pass
    learner = ShardedLearner(_cfg(**SOURCE), OBS, ACT, 1.0, 0.0, chunk_size=3)
    learner.run_chunk_async(jax.device_put(jnp.zeros((3, B, 2 * OBS + ACT + 3), jnp.float32), learner._chunk_sharding))
    scopes = set(learner.chunk_ops()["ops"].values())
    assert {"update/critic/norm", "update/actor/norm"} <= scopes and "update/polyak" not in scopes


def test_adam_b1_unset_is_every_other_programs_text():
    """`adam_b1` at its default, spelt out or not, is one lowered program,
    for the plain step as for sac's; 0.5 is another."""
    rng = np.random.default_rng(1)
    for kw in (dict(sac=False), dict(sac=True), dict(sac=False, twin_critic=True, policy_delay=2)):
        plain, spelt, half = _cfg(**kw), _cfg(**kw, adam_b1=0.9), _cfg(**kw, adam_b1=0.5)
        state = init_train_state(plain, OBS, ACT, seed=0)
        texts = [jax.jit(make_learner_step(c, 1.0)).lower(state, _batch(rng)).as_text() for c in (plain, spelt, half)]
        assert texts[0] == texts[1] and texts[0] != texts[2]


def test_adam_leaves_a_leaf_with_a_zero_gradient_where_it_is():
    p = {"w": jnp.asarray([1.0, -2.0]), "stat": jnp.asarray([0.123456789, 7.0])}
    g = {"w": jnp.asarray([0.5, 0.25]), "stat": jnp.zeros(2)}
    opt = OptState(mu=jax.tree.map(jnp.zeros_like, p), nu=jax.tree.map(jnp.zeros_like, p), count=jnp.zeros((), jnp.int32))
    for _ in range(3):
        new, opt = adam_update(p, g, opt, 1e-3, 0.5)
        np.testing.assert_array_equal(new["stat"], p["stat"])
        assert not np.any(np.asarray(opt.mu["stat"])) and not np.any(np.asarray(opt.nu["stat"]))
        assert np.all(np.asarray(new["w"]) < np.asarray(p["w"]))
        p = new


def test_the_folded_mlp_is_the_evaluation_mode_actor():
    """What leaves the learner: each BN layer folded into the dense layer
    behind it. The plain MLP gives the evaluation-mode head to 1e-6, has the
    layout the workers already use, and `NumpyPolicy` acts as `make_act_fn`."""
    cfg = _cfg(**SOURCE)
    state = _moved(cfg)
    actor = jax.device_get(state.actor_params)
    assert float(np.abs(actor[0]["bn_mean"]).max()) > 0.05 and float(np.abs(actor[1]["bn_shift"]).max()) > 0
    folded = mlp.fold_norm(actor)
    assert all(set(layer) == {"w", "b"} for layer in folded)
    assert [(l["w"].shape, l["b"].shape) for l in folded] == param_layout(OBS, actor_head_dim(ACT, True), (16, 16))
    obs = _batch(np.random.default_rng(9), 64).obs
    mean, log_std = mlp.actor_gaussian_apply(state.actor_params, obs, cfg.sac_log_std_min, cfg.sac_log_std_max)
    plain_mean, plain_log_std = mlp.actor_gaussian_apply(
        jax.tree.map(jnp.asarray, folded), obs, cfg.sac_log_std_min, cfg.sac_log_std_max)
    np.testing.assert_allclose(np.asarray(plain_mean), np.asarray(mean), rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(plain_log_std), np.asarray(log_std), rtol=0, atol=1e-6)
    # training mode is another function of the same parameters
    (train_mean, _), moments = mlp.actor_gaussian_apply(
        state.actor_params, obs, cfg.sac_log_std_min, cfg.sac_log_std_max, train=True)
    assert len(moments) == 3 and float(jnp.max(jnp.abs(train_mean - mean))) > 1e-3
    policy = NumpyPolicy(param_layout(OBS, actor_head_dim(ACT, True), (16, 16)), 0.4, 0.1, gaussian=True)
    policy.load_flat(flatten_params(folded))
    act = make_act_fn(cfg, 0.4, 0.1)
    np.testing.assert_allclose(policy(np.asarray(obs)), np.asarray(act(state.actor_params, obs)), rtol=0, atol=1e-6)
    # a plain net comes back as it is; and the learner's own hand-off folds
    plain = jax.device_get(init_train_state(_cfg(), OBS, ACT, 0).actor_params)
    assert mlp.fold_norm(plain) is plain
    learner = ShardedLearner(cfg, OBS, ACT, 0.4, 0.1, chunk_size=2)
    learner.state = jax.device_put(state, learner._state_sharding)
    for ours, theirs in zip(learner.actor_params_to_host(), folded):
        assert set(ours) == {"w", "b"}
        np.testing.assert_array_equal(ours["w"], theirs["w"])
        np.testing.assert_array_equal(ours["b"], theirs["b"])


def test_partition_rules_place_the_new_state():
    """Every leaf of a state without targets is placed on purpose: the BN
    vectors replicate, the dense layers shard as they did, the target slots
    stay empty nodes, and a chunk runs under tensor parallelism."""
    cfg = _cfg(**SOURCE, batch_size=8)
    state = init_train_state(cfg, OBS, ACT, seed=0)
    mesh = mesh_lib.make_mesh(4, 2)
    spec = mesh_lib.state_pspec(state, mesh)
    assert spec.target_actor_params is None and spec.target_critic_params is None
    assert jax.tree.structure(spec, is_leaf=lambda x: isinstance(x, P)) == jax.tree.structure(
        jax.tree.map(lambda x: P(), state), is_leaf=lambda x: isinstance(x, P))
    for layer in (*spec.actor_params, *spec.critic_params, *spec.critic_opt.mu):
        for k in ("bn_scale", "bn_shift", "bn_mean", "bn_var"):
            assert all(axis is None for axis in layer[k])
    assert spec.critic_params[1]["w"] == P(None, "model", None) and spec.critic_params[0]["b"] == P(None, "model")
    learner = ShardedLearner(cfg.replace(model_axis=2), OBS, ACT, 1.0, 0.0, chunk_size=2, mesh=mesh)
    packed = jnp.asarray(
        np.random.default_rng(5).standard_normal((2, learner.global_batch, 2 * OBS + ACT + 3)), jnp.float32)
    out = learner._chunk_step(learner.state, jax.device_put(packed, learner._chunk_sharding))
    assert int(out.state.step) == 2 and np.isfinite(float(out.metrics["bn_stat_gap"]))
    assert out.state.critic_params[1]["w"].sharding.spec == P(None, "model", None)


def test_counts_follow_the_one_rule_across_launches():
    """Three launches of 7 updates at a delay of 3 on a data mesh of two: the
    actor's and the temperature's Adam counts are delayed_updates(steps, 3),
    the record's `crossq_policy_updates` (train.delay_fields: the same rule
    on the same step count), whatever phase a launch starts in; the critics'
    count is the step count."""
    cfg = _cfg(**SOURCE, batch_size=8)
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


def test_checkpoint_round_trip_of_a_state_without_targets(tmp_path):
    """Saved from a data mesh of 8 and restored under (4, 2): the bits are
    the saved ones, statistics and all, the target slots stay None, the chunk
    runs from there; and a plain sac run refuses the checkpoint by name."""
    from distributed_ddpg_tpu import checkpoint as ckpt_lib

    cfg = _cfg(**SOURCE, batch_size=8)
    state = _moved(cfg, updates=3)
    mesh1 = mesh_lib.make_mesh(8, 1)
    placed = jax.device_put(state, mesh_lib.to_named(mesh1, mesh_lib.state_pspec(state, mesh1)))
    ckpt_lib.save(str(tmp_path / "a"), 3, placed, config=cfg)
    template = init_train_state(cfg, OBS, ACT, seed=1)
    restored, at, _ = ckpt_lib.restore(str(tmp_path / "a"), template, config=cfg)
    assert at == 3 and restored.target_actor_params is None and restored.target_critic_params is None
    assert jax.tree.structure(restored) == jax.tree.structure(state)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(jax.device_get(state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(np.abs(restored.critic_params[0]["bn_mean"]).max()) > 0
    learner = ShardedLearner(cfg.replace(model_axis=2), OBS, ACT, 1.0, 0.0, chunk_size=2, mesh=mesh_lib.make_mesh(4, 2))
    learner.state = jax.device_put(restored, learner._state_sharding)
    packed = jnp.asarray(
        np.random.default_rng(5).standard_normal((2, learner.global_batch, 2 * OBS + ACT + 3)), jnp.float32)
    out = learner._chunk_step(learner.state, jax.device_put(packed, learner._chunk_sharding))
    assert int(out.state.step) == 5
    plain = cfg.replace(crossq=False)
    with pytest.raises(ValueError, match="crossq: checkpoint=True run=False"):
        ckpt_lib.restore(str(tmp_path / "a"), init_train_state(plain, OBS, ACT, seed=1), config=plain)


def test_a_checkpoint_from_before_the_field_is_no_crossq_runs(tmp_path):
    """tests/ckpt_fixtures/parent_pr33 (config_3.json has no `crossq` key)
    still passes the compatibility check of a run like the one that wrote it,
    and a CrossQ run is told why it cannot take it."""
    import os
    import shutil

    from distributed_ddpg_tpu import checkpoint as ckpt_lib

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ckpt_fixtures", "parent_pr33")
    directory = str(tmp_path / "ckpt")
    shutil.copytree(src, directory)
    assert "crossq" not in json.load(open(os.path.join(directory, "config_3.json")))
    writer = DDPGConfig(actor_hidden=(16, 16), critic_hidden=(16, 16), batch_size=8, seed=3)
    ckpt_lib.check_config_compatible(directory, 3, writer)
    with pytest.raises(ValueError, match="crossq: checkpoint=False run=True"):
        ckpt_lib.check_config_compatible(
            directory, 3, writer.replace(sac=True, crossq=True, actor_hidden=(16, 16), critic_hidden=(16, 16)))


def test_train_runs_crossq_end_to_end_and_its_records_say_so(tmp_path):
    """The normal path at a small size: host actors acting on the folded
    policy, the device ring, run_sample_chunk on the scan leg, refresh, a
    checkpoint and a resume; the delay's counter is the actor's Adam count;
    a plain sac run's records have none of the keys."""
    from distributed_ddpg_tpu.train import train

    def run(name, *extra):
        log = tmp_path / f"{name}.jsonl"
        cfg = DDPGConfig.from_flags([
            "--backend=jax_tpu", "--env_id=Pendulum-v1", "--sac=true", "--num_actors=2",
            "--total_env_steps=1500", "--replay_min_size=300", "--eval_every=0", "--actor_hidden=16,16",
            "--critic_hidden=32,32", "--replay_capacity=4096", "--batch_size=16", "--learner_chunk=10",
            "--max_ingest_ratio=2", f"--log_path={log}", f"--checkpoint_dir={tmp_path / name}",
            "--checkpoint_every=200", *extra,
        ])
        summary = train(cfg)
        return cfg, summary, [json.loads(line) for line in open(log)]

    flags = ("--crossq=true", "--policy_delay=3", "--adam_b1=0.5", "--action_insert_layer=0")
    cfg, summary, records = run("crossq", *flags)
    assert summary["fused_chunk_active"] is False and summary["crossq"] is True
    assert summary["learner_steps"] >= 400
    assert summary["crossq_policy_updates"] == delayed_updates(summary["learner_steps"], 3)
    header = next(r for r in records if r["kind"] == "header")
    final = next(r for r in records if r["kind"] == "final")
    assert header["crossq"] is True and final["crossq"] is True
    assert final["crossq_policy_updates"] == summary["crossq_policy_updates"]
    assert np.isfinite(final["bn_stat_gap"]) and final["bn_stat_gap"] > 0
    assert summary["param_checksum"] != summary["param_checksum_start"]
    table = json.load(open(summary["chunk_ops_path"]))
    assert "update/polyak" not in set(table["ops"].values())
    assert {"update/critic/norm", "update/actor/norm"} <= set(table["ops"].values())
    # and a second run resumes from it
    _, resumed, _ = run("crossq", *flags, "--total_env_steps=2000")
    assert resumed["learner_steps"] > summary["learner_steps"]
    _, plain, plain_records = run("sac")
    for r in plain_records + [plain]:
        assert not {"bn_stat_gap", "crossq_policy_updates", "crossq"} & set(r)
