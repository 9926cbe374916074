"""DMPO (config.mpo) outside its comparison with the reference
(tests/test_reference_dmpo.py holds that): what config.py refuses, the
E-step's weights, the targets' copy rule, the LayerNormMLP policy on the host
and behind both serving engines, the partition rules, the checkpoint with the
dual variables' tree, the scope vocabulary, and a run through train()."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.actors import policy as host
from distributed_ddpg_tpu.actors.policy import NumpyPolicy, flatten_params, layout_of, param_layout
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.learner import init_train_state, make_act_fn, make_learner_step, make_sample_fn
from distributed_ddpg_tpu.models import mlp
from distributed_ddpg_tpu.ops import fused_chunk, losses, polyak
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.types import Batch

OBS, ACT, B = 7, 3, 8
SOURCE = dict(
    mpo=True, distributional=True, action_insert_layer=0, n_step=5, num_atoms=11, v_min=-5.0, v_max=5.0,
    actor_hidden=(16, 16, 8), critic_hidden=(32, 16, 8), batch_size=B, mpo_samples=4,
    actor_lr=1e-3, critic_lr=1e-3, target_update_period=2,
)
LAYOUT = param_layout(OBS, 2 * ACT, SOURCE["actor_hidden"], lnmlp=True)


def _cfg(**kw):
    return DDPGConfig(**{**SOURCE, **kw})


def _batch(rng, n=B):
    return Batch(
        obs=rng.standard_normal((n, OBS)).astype(np.float32),
        action=(0.1 + 0.4 * rng.uniform(-1, 1, (n, ACT))).astype(np.float32),
        reward=rng.standard_normal(n).astype(np.float32),
        discount=np.full(n, 0.95, np.float32),
        next_obs=rng.standard_normal((n, OBS)).astype(np.float32),
        weight=np.ones(n, np.float32),
    )


@pytest.fixture(scope="module")
def moved():
    """A state three updates off its seed: LayerNorm's vectors off 1 and 0,
    the head's two halves off their seeded thousandths."""
    cfg = _cfg()
    state = init_train_state(cfg, OBS, ACT, seed=3)
    wide = lambda net: (*net[:-1], {k: 100.0 * v for k, v in net[-1].items()})
    state = state._replace(actor_params=wide(state.actor_params), target_actor_params=wide(state.target_actor_params))
    step = jax.jit(make_learner_step(cfg, 0.4, action_offset=0.1))
    rng = np.random.default_rng(0)
    for _ in range(3):
        state = step(state, _batch(rng)).state
    return state


REFUSED = {
    "without_the_categorical_critic": (dict(distributional=False), "distributional=True"),
    "twin_critic": (dict(twin_critic=True), "separate|distributional"),
    "sac": (dict(sac=True), "sac is its own|distributional"),
    "pixels": (dict(pixels=True), "pixels"),
    "action_at_the_second_layer": (dict(action_insert_layer=1), "action_insert_layer=0"),
    "one_sample": (dict(mpo_samples=1), "mpo_samples"),
    "epsilon_zero": (dict(mpo_epsilon_mean=0.0), "mpo_epsilon_mean must be > 0"),
    "dual_lr_zero": (dict(dual_lr=0.0), "dual_lr must be > 0"),
    "native_backend": (dict(backend="native"), "backend='jax_tpu'"),
    "fused_chunk_on": (dict(fused_chunk="on"), "fused_chunk=on"),
    "prioritized": (dict(prioritized=True), "prioritized"),
    "device_actors": (dict(actor_backend="device", num_actors=0), "host actors only"),
    "a_noise_process": (dict(exploration="gaussian"), "exploration"),
    "negative_period": (dict(target_update_period=-1), "target_update_period must be >= 0"),
}
PERIOD_REFUSED = {
    "crossq": dict(sac=True, crossq=True, policy_delay=3, action_insert_layer=0),
    "simba": dict(sac=True, simba=True, action_insert_layer=0, actor_hidden=(16,), critic_hidden=(16, 16)),
    "delayed_twin_critic": dict(twin_critic=True, policy_delay=2),
    "native_backend": dict(backend="native"),
    "fused_chunk_on": dict(fused_chunk="on"),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_config_refuses_with_a_message(name):
    kw, message = REFUSED[name]
    with pytest.raises(ValueError, match=message):
        _cfg(**kw)


@pytest.mark.parametrize("name", sorted(PERIOD_REFUSED))
def test_a_copy_period_is_refused_where_the_targets_are_not_whole_nets_on_the_step_count(name):
    with pytest.raises(ValueError, match="target_update_period"):
        DDPGConfig(target_update_period=100, **PERIOD_REFUSED[name])


@pytest.mark.parametrize("kw", [SOURCE, dict(target_update_period=100)])
def test_the_kernel_is_not_supported_and_the_learner_takes_the_scan_leg(kw):
    cfg = DDPGConfig(**kw)
    assert not fused_chunk.supported(cfg)
    learner = ShardedLearner(cfg, OBS, ACT, 1.0, 0.0, chunk_size=2, mesh=mesh_lib.make_mesh(devices=jax.devices()[:1]))
    assert not learner.fused_chunk_active


def test_the_front_rounds_the_observations_and_leaves_the_action_whole():
    """The critic divides the ring's action by the box's half-width before
    its first product: the cut kernel hands it on in float32 (rounded first,
    it would be rounded twice) and the two observations in bfloat16, as every
    family's whose readers are matmuls."""
    from distributed_ddpg_tpu.ops import chunk_front

    assert chunk_front.rounds_inputs(_cfg()) and not chunk_front.rounds_action(_cfg())
    sac = DDPGConfig(sac=True)
    assert chunk_front.rounds_inputs(sac) and chunk_front.rounds_action(sac)
    crossq = DDPGConfig(sac=True, crossq=True, policy_delay=3, action_insert_layer=0)
    assert not chunk_front.rounds_inputs(crossq) and not chunk_front.rounds_action(crossq)
    packed = jnp.asarray(np.random.default_rng(0).standard_normal((2, 128, 2 * OBS + ACT + 3)), jnp.float32)
    cut = chunk_front.cut_rows(packed, OBS, ACT, True, interpret=True, action_rounded=False)
    np.testing.assert_array_equal(cut.action, packed[..., OBS:OBS + ACT])
    np.testing.assert_array_equal(cut.obs, packed[..., :OBS].astype(jnp.bfloat16).astype(jnp.float32))
    both = chunk_front.cut_rows(packed, OBS, ACT, True, interpret=True)
    np.testing.assert_array_equal(both.action, packed[..., OBS:OBS + ACT].astype(jnp.bfloat16).astype(jnp.float32))


def test_the_state_is_two_layernorm_mlps_and_the_dual_tree():
    cfg = _cfg()
    state = init_train_state(cfg, OBS, ACT, seed=0)
    assert mlp.is_lnmlp(state.actor_params) and mlp.is_lnmlp(state.critic_params)
    assert not mlp.is_simba(state.actor_params) and cfg.gaussian_head
    assert [sorted(layer) for layer in state.actor_params] == [["b", "w"], ["ln_scale", "ln_shift"]] + 3 * [["b", "w"]]
    assert state.actor_params[-1]["w"].shape == (8, 2 * ACT) and state.critic_params[0]["w"].shape == (OBS + ACT, 32)
    assert state.critic_params[-1]["w"].shape == (8, 11)
    duals = state.log_alpha
    assert tuple(sorted(duals)) == tuple(sorted(losses.MPO_DUALS))
    assert duals["log_temperature"].shape == duals["log_penalty_temperature"].shape == (1,)
    assert duals["log_alpha_mean"].shape == duals["log_alpha_stddev"].shape == (ACT,)
    assert float(duals["log_alpha_stddev"][0]) == 1000.0 and float(duals["log_temperature"][0]) == 10.0
    assert jax.tree.structure(state.alpha_opt.mu) == jax.tree.structure(duals)
    # a residual net is no LayerNormMLP, nor is a plain one
    simba = init_train_state(DDPGConfig(sac=True, simba=True, action_insert_layer=0, actor_hidden=(16,), critic_hidden=(16,)), OBS, ACT, 0)
    assert not mlp.is_lnmlp(simba.actor_params) and not mlp.is_lnmlp(init_train_state(DDPGConfig(), OBS, ACT, 0).actor_params)


@pytest.mark.parametrize("temperature,uniform", [(0.05, False), (1.0, False), (1e4, True)])
def test_the_weights_sum_to_one_and_fall_to_uniform_as_the_temperature_grows(temperature, uniform):
    q = jnp.asarray(np.random.default_rng(1).standard_normal((6, 5)), jnp.float32)
    w = losses.mpo_weights(q, jnp.asarray([temperature]))
    np.testing.assert_allclose(jnp.sum(w, axis=1), 1.0, rtol=1e-6)
    ess = 1.0 / jnp.sum(jnp.square(w), axis=1)
    assert bool(jnp.all(ess > 4.999)) == uniform and bool(jnp.all((ess >= 1.0) & (ess <= 5.0 + 1e-5)))
    np.testing.assert_array_equal(jnp.argmax(w, axis=1), jnp.argmax(q, axis=1))
    # the temperature's loss has zero slope where the weights' mean KL from uniform is epsilon
    kl = float(jnp.mean(jnp.sum(w * jnp.log(w * 5.0), axis=1)))
    slope = float(jax.grad(lambda t: losses.mpo_temperature_loss(q, kl, t))(jnp.asarray([temperature]))[0])
    assert abs(slope) < 1e-4


def test_the_out_of_box_cost_is_zero_inside_the_box():
    a = jnp.asarray([[[0.5, -1.0, 1.0], [1.5, 0.0, -3.0]]])
    np.testing.assert_allclose(losses.mpo_out_of_box_cost(a), [[0.0, -np.hypot(0.5, 2.0)]], rtol=1e-6)


def test_the_kls_are_zero_at_the_target_and_each_reads_its_own_half():
    mean_t, std_t = jnp.zeros((4, ACT)), jnp.full((4, ACT), 0.7)
    zero = losses.mpo_kls(mean_t, std_t, mean_t, std_t)
    np.testing.assert_allclose(zero[0], 0.0, atol=1e-7)
    np.testing.assert_allclose(zero[1], 0.0, atol=1e-7)
    kl_mean, kl_std = losses.mpo_kls(mean_t + 0.07, std_t, mean_t, std_t)
    np.testing.assert_allclose(kl_mean, 0.005, rtol=1e-5)  # (0.07 / 0.7)^2 / 2
    np.testing.assert_allclose(kl_std, 0.0, atol=1e-7)
    kl_mean, kl_std = losses.mpo_kls(mean_t, 2.0 * std_t, mean_t, std_t)
    np.testing.assert_allclose(kl_mean, 0.0, atol=1e-7)
    np.testing.assert_allclose(kl_std, np.log(2.0) + 0.125 - 0.5, rtol=1e-5)


@pytest.mark.parametrize("family", ["ddpg", "d4pg", "sac", "mpo"])
def test_a_copy_period_of_one_is_tau_one(family):
    """`target_update_period=1` copies after every update: the state tau = 1
    leaves, to the bit, targets included."""
    kw = {
        "ddpg": {}, "d4pg": dict(distributional=True, num_atoms=11),
        "sac": dict(sac=True), "mpo": {k: v for k, v in SOURCE.items() if k != "target_update_period"},
    }[family]
    kw = dict(dict(actor_hidden=(16, 8), critic_hidden=(16, 8), batch_size=B), **kw)
    rng = np.random.default_rng(2)
    batches = [_batch(rng) for _ in range(3)]
    ends = []
    for how in (dict(target_update_period=1), dict(tau=1.0)):
        cfg = DDPGConfig(**kw, **how)
        state, step = init_train_state(cfg, OBS, ACT, 0), jax.jit(make_learner_step(cfg, 0.4, action_offset=0.1))
        for b in batches:
            state = step(state, b).state
        ends.append(state)
    for a, b in zip(jax.tree.leaves(ends[0]), jax.tree.leaves(ends[1])):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(ends[0].critic_params), jax.tree.leaves(ends[0].target_critic_params)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("steps,period,copies", [(0, 100, 0), (99, 100, 0), (100, 100, 1), (800, 100, 8), (7, 3, 2), (5, 1, 5)])
def test_target_copies_counts_the_updates_that_ended_a_period(steps, period, copies):
    assert polyak.target_copies(steps, period) == copies
    online, target = {"w": jnp.ones(3)}, {"w": jnp.zeros(3)}
    ended = sum(
        bool(polyak.target_update(online, target, 0.5, jnp.asarray(k, jnp.int32), period)["w"][0] == 1.0)
        for k in range(steps)
    )
    assert ended == copies


def test_period_zero_is_polyaks_average():
    online, target = {"w": jnp.ones(3)}, {"w": jnp.zeros(3)}
    np.testing.assert_array_equal(polyak.target_update(online, target, 0.25, jnp.asarray(7), 0)["w"], 0.25)


def test_the_two_files_hold_one_set_of_constants():
    assert (host.LNMLP_EPS, host.GAUSSIAN_INIT_SCALE, host.GAUSSIAN_MIN_SCALE) == (
        mlp.LNMLP_EPS, mlp.GAUSSIAN_INIT_SCALE, mlp.GAUSSIAN_MIN_SCALE)
    assert {"tanh", "elu"} <= set(host.KINDS)
    assert [e[2] for e in LAYOUT] == ["linear", "tanh", "elu", "elu", "linear"]
    assert LAYOUT[0][0] == (OBS, 16) and LAYOUT[-1][0] == (8, 2 * ACT)
    assert layout_of(_cfg(), OBS, ACT) == LAYOUT


def test_the_numpy_policy_is_the_learners_policy_and_its_draw_stays_in_the_box(moved):
    """actors/policy.py's `tanh` and `elu` kinds against models/mlp.py to
    1e-6: the head, the action on the mean (what the evaluator acts on), and
    a thousand draws, every one inside the environment's box, their mean and
    spread the Gaussian's where it lies inside."""
    params = jax.device_get(moved.actor_params)
    flat = flatten_params(mlp.fold_norm(params))
    assert flat.size == host.layout_size(LAYOUT)
    obs = np.random.default_rng(4).standard_normal((5, OBS)).astype(np.float32)
    mean, std = (np.asarray(x) for x in mlp.gaussian_apply(moved.actor_params, obs))
    greedy = NumpyPolicy(LAYOUT, 0.4, 0.1, gaussian=True, squash=False)
    greedy.load_flat(flat)
    np.testing.assert_allclose(greedy.head(obs)[:, :ACT], mean, rtol=0, atol=1e-6)
    np.testing.assert_allclose(greedy(obs), np.clip(mean, -1, 1) * 0.4 + 0.1, rtol=0, atol=1e-6)
    np.testing.assert_allclose(
        greedy(obs), make_act_fn(_cfg(), 0.4, 0.1)(moved.actor_params, obs), rtol=0, atol=1e-6)
    for a, b in zip(jax.tree.leaves(greedy.tree()), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b)
    assert jax.tree.structure(greedy.tree()) == jax.tree.structure(params)
    sampler = NumpyPolicy(LAYOUT, 0.4, 0.1, gaussian=True, stochastic=True, seed=1, squash=False)
    sampler.load_flat(flat)
    draws = np.stack([sampler(obs[:1])[0] for _ in range(1000)])
    assert draws.min() >= 0.1 - 0.4 - 1e-6 and draws.max() <= 0.1 + 0.4 + 1e-6
    assert (np.abs(draws - (0.1 - 0.4)) < 1e-6).any() or (np.abs(draws - (0.1 + 0.4)) < 1e-6).any()  # some were clipped
    inside = np.abs(mean[0]) + 3 * std[0] < 1.0
    if inside.any():
        np.testing.assert_allclose(draws.mean(0)[inside], (mean[0] * 0.4 + 0.1)[inside], atol=0.05)
    # the learner's own sampler clips alike
    dev = make_sample_fn(_cfg(), 0.4, 0.1)(moved.actor_params, np.repeat(obs[:1], 256, 0), jax.random.PRNGKey(0))
    assert float(dev.min()) >= -0.3 - 1e-6 and float(dev.max()) <= 0.5 + 1e-6


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_the_serving_engine_answers_with_the_gaussian_policy(moved, backend):
    """serve/server.py, both backends, on the flat block the pool
    broadcasts: head rows [mean | log scale] of `gaussian_apply` to 1e-5,
    the deterministic action the clipped mean, a sampled one inside the box."""
    from distributed_ddpg_tpu.serve import InferenceServer

    flat = flatten_params(mlp.fold_norm(jax.device_get(moved.actor_params)))
    server = InferenceServer(
        LAYOUT, np.full(ACT, 0.4, np.float32), 0.1, max_batch=8, backend=backend, sac=True, squash=False,
    )
    server.refresh(flat)
    obs = np.random.default_rng(5).standard_normal((6, OBS)).astype(np.float32)
    mean, std = (np.asarray(x) for x in mlp.gaussian_apply(moved.actor_params, obs))
    heads = server._compute(obs)
    np.testing.assert_allclose(heads[:, :ACT], mean, rtol=0, atol=1e-5)
    np.testing.assert_allclose(heads[:, ACT:], np.log(std), rtol=0, atol=1e-5)
    action = server.sample(heads[0], tenant="t", request_id=1, explore=False)
    np.testing.assert_allclose(action, np.clip(mean[0], -1, 1) * 0.4 + 0.1, rtol=0, atol=1e-5)
    drawn = np.stack([server.sample(heads[0], tenant="t", request_id=i) for i in range(200)])
    assert drawn.min() >= -0.3 - 1e-6 and drawn.max() <= 0.5 + 1e-6 and drawn.std() > 0.01


@pytest.mark.parametrize("model_axis", [1, 2])
def test_partition_rules_place_every_leaf(model_axis):
    """The first layer and its LayerNorm replicate, the dense layers behind
    alternate column and row, the output layer and every dual variable
    replicate, Adam's moments follow their parameters; and a chunk runs."""
    cfg = _cfg()
    state = init_train_state(cfg, OBS, ACT, seed=0)
    mesh = mesh_lib.make_mesh(8 // model_axis, model_axis)
    spec = mesh_lib.state_pspec(state, mesh)
    assert jax.tree.structure(spec, is_leaf=lambda x: isinstance(x, P)) == jax.tree.structure(
        jax.tree.map(lambda x: P(), state), is_leaf=lambda x: isinstance(x, P))
    for tree in (spec.actor_params, spec.critic_params, spec.target_actor_params, spec.critic_opt.nu):
        for layer in (tree[0], tree[1], tree[-1]):
            assert all(axis is None for s in layer.values() for axis in s)
    for tree in (spec.log_alpha, spec.alpha_opt.mu, spec.alpha_opt.nu):
        assert all(s == P() for s in tree.values())
    if model_axis == 2:
        assert spec.critic_params[2]["w"] == P(None, "model") and spec.critic_params[3]["w"] == P("model", None)
        assert spec.actor_opt.mu[2]["b"] == P("model")
    learner = ShardedLearner(cfg.replace(model_axis=model_axis), OBS, ACT, 0.4, 0.1, chunk_size=2, mesh=mesh)
    packed = jnp.asarray(
        np.random.default_rng(5).standard_normal((2, learner.global_batch, 2 * OBS + ACT + 3)), jnp.float32)
    out = learner._chunk_step(learner.state, jax.device_put(packed, learner._chunk_sharding))
    assert int(out.state.step) == 2 and np.isfinite(float(out.metrics["mpo_weight_ess"]))


def test_checkpoint_round_trips_the_dual_tree(tmp_path, moved):
    """Saved from a data mesh of 8 and restored: the bits are the saved ones,
    the four dual variables and their Adam's moments with them; a plain D4PG
    run refuses the checkpoint by name."""
    from distributed_ddpg_tpu import checkpoint as ckpt_lib

    cfg = _cfg()
    mesh = mesh_lib.make_mesh(8, 1)
    placed = jax.device_put(moved, mesh_lib.to_named(mesh, mesh_lib.state_pspec(moved, mesh)))
    ckpt_lib.save(str(tmp_path / "a"), 3, placed, config=cfg)
    restored, at, _ = ckpt_lib.restore(str(tmp_path / "a"), init_train_state(cfg, OBS, ACT, seed=9), config=cfg)
    assert at == 3 and jax.tree.structure(restored) == jax.tree.structure(moved)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(jax.device_get(moved))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(restored.log_alpha["log_temperature"][0]) != 10.0 and int(restored.alpha_opt.count) == 3
    plain = DDPGConfig(**{**SOURCE, "mpo": False})
    with pytest.raises(ValueError, match="mpo: checkpoint=True run=False"):
        ckpt_lib.restore(str(tmp_path / "a"), init_train_state(plain, OBS, ACT, seed=1), config=plain)


def test_the_scope_vocabulary_has_the_estep_and_the_duals():
    assert {"update/estep", "update/estep/lnorm", "update/duals", "update/duals/optim"} <= set(trace.CHUNK_SCOPES)
    cfg = _cfg()
    learner = ShardedLearner(cfg, OBS, ACT, 0.4, 0.1, chunk_size=4, mesh=mesh_lib.make_mesh(devices=jax.devices()[:1]))
    packed = jax.ShapeDtypeStruct((4, B, 2 * OBS + ACT + 3), jnp.float32)
    text = learner._chunk_step.lower(learner.state, packed).compile().as_text()
    found = set(trace.chunk_ops_table(text)["ops"].values())
    assert {"update/estep", "update/duals", "update/critic", "update/actor", "update/optim", "update/polyak", "noise"} <= found
    assert found <= set(trace.CHUNK_SCOPES)


def test_train_runs_dmpo_end_to_end_and_its_records_say_so(tmp_path, one_chip):
    """The normal path at a small size, on one device as the cell's (the
    conftest's `one_chip`): host workers sampling the layered numpy policy's
    Gaussian, 5-step rows, the device ring, run_sample_chunk on the scan leg,
    the refresh and the evaluator on the mean; a plain D4PG program has none
    of the keys."""
    from distributed_ddpg_tpu.learner import MPO_KEYS, metric_keys
    from distributed_ddpg_tpu.train import train

    log = tmp_path / "dmpo.jsonl"
    cfg = DDPGConfig.from_flags([
        "--backend=jax_tpu", "--env_id=Pendulum-v1", "--mpo=true", "--distributional=true", "--num_atoms=11",
        "--n_step=5", "--mpo_samples=4", "--action_insert_layer=0", "--actor_hidden=16,16,8",
        "--critic_hidden=32,16,8", "--target_update_period=25", "--num_actors=2", "--total_env_steps=1200",
        "--replay_min_size=300", "--eval_every=500", "--eval_episodes=1", "--replay_capacity=4096",
        "--batch_size=16", "--learner_chunk=10", "--max_ingest_ratio=1", f"--log_path={log}",
    ])
    summary = train(cfg)
    records = [json.loads(line) for line in open(log)]
    assert summary["fused_chunk_active"] is False and summary["learner_steps"] >= 200
    header = next(r for r in records if r["kind"] == "header")
    final = next(r for r in records if r["kind"] == "final")
    facts = {"mpo_samples": 4, "mpo_duals": 2 + 2 * 1, "target_update_period": 25}  # Pendulum: one action
    for r in (header, final, summary):
        assert {k: r[k] for k in facts} == facts
    assert final["target_copies"] == summary["learner_steps"] // 25 == summary["target_copies"]
    assert 1.0 <= final["mpo_weight_ess"] <= 4.0 + 1e-4 and final["mpo_kl_mean_ratio"] >= 0.0
    assert 0.0 < final["mpo_temperature"] < 10.0 and 0.0 <= final["c51_edge_mass"] <= 1.0
    assert any(r["kind"] == "eval" and np.isfinite(r["eval_return"]) for r in records)
    assert summary["param_checksum"] != summary["param_checksum_start"]
    table = json.load(open(summary["chunk_ops_path"]))
    assert {"update/estep", "update/duals", "update/polyak"} <= set(table["ops"].values())
    assert not set(MPO_KEYS) & set(metric_keys(DDPGConfig(distributional=True)))
