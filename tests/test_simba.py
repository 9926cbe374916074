"""SimBa (arXiv 2410.09754) as a configuration of the SAC step: residual
pre-LayerNorm nets behind a running-statistics input normaliser, AdamW. What
tests/test_reference_simba.py leaves: the gates, the leg, the state's shape,
that every other family's program is untouched, the policy that leaves the
learner (layered: it does not fold into dense layers) in the host workers'
numpy, the evaluator, both serving engines and the network front, the
partition rules, the checkpoint, and a run through train()."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_ddpg_tpu import trace
from distributed_ddpg_tpu.actors.policy import (
    NumpyPolicy, actor_head_dim, flatten_params, is_layered, layout_size, param_layout,
)
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.learner import init_train_state, jit_learner_step, make_act_fn, make_learner_step
from distributed_ddpg_tpu.models import mlp
from distributed_ddpg_tpu.ops import fused_chunk
from distributed_ddpg_tpu.ops.optim import adam_update
from distributed_ddpg_tpu.ops.polyak import polyak_update
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.types import Batch, OptState

OBS, ACT, B = 5, 2, 16
SOURCE = dict(
    simba=True, action_insert_layer=0, weight_decay=1e-2, sac_alpha=0.01, target_entropy_scale=0.5,
    actor_lr=1e-3, critic_lr=1e-3, tau=0.005, actor_hidden=(16,), critic_hidden=(32, 32),
)
LAYOUT = param_layout(OBS, actor_head_dim(ACT, True), (16,), residual=True)


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
    """A state a few updates off its seed: statistics, LayerNorm scales and
    shifts have all left their identity values."""
    state = init_train_state(cfg, OBS, ACT, seed=0)
    step = jit_learner_step(cfg, 1.0, donate=False)
    rng = np.random.default_rng(seed)
    for _ in range(updates):
        state = step(state, _batch(rng)).state
    return state


@pytest.fixture(scope="module")
def moved():
    return _moved(_cfg(**SOURCE))


REFUSED = {
    "without_sac": (dict(sac=False), "simba is plain sac"),
    "with_crossq": (dict(crossq=True), "simba is plain sac"),
    "with_an_ensemble": (dict(critic_ensemble=5), "simba is plain sac"),
    "action_at_layer_1": (dict(action_insert_layer=1), "action_insert_layer=0"),
    "blocks_of_two_widths": (dict(critic_hidden=(32, 64)), "one residual block per entry"),
    "negative_decay": (dict(weight_decay=-1.0), "weight_decay must be >= 0"),
    "zero_entropy_scale": (dict(target_entropy_scale=0.0), "target_entropy_scale must be > 0"),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_config_refuses_with_a_message(name):
    kw, message = REFUSED[name]
    with pytest.raises(ValueError, match=message):
        _cfg(**{**SOURCE, **kw})


def test_decay_is_refused_by_the_native_backend():
    with pytest.raises(ValueError, match="weight_decay is read by the tree-level Adam"):
        DDPGConfig(backend="native", weight_decay=1e-2)


@pytest.mark.parametrize("kw", [SOURCE, dict(weight_decay=1e-2)])
def test_the_kernel_is_not_supported_and_the_learner_takes_the_scan_leg(kw):
    cfg = _cfg(**kw)
    assert not fused_chunk.supported(cfg)
    assert fused_chunk.supported(_cfg())  # plain sac at this size still is
    learner = ShardedLearner(cfg.replace(fused_chunk="auto"), OBS, ACT, 1.0, 0.0, chunk_size=4)
    assert not learner.fused_chunk_active


def test_the_state_is_the_residual_tree():
    cfg = _cfg(**SOURCE)
    state = init_train_state(cfg, OBS, ACT, seed=0)
    embed, *blocks, head = state.critic_params
    assert set(embed) == {"w", "b", "rs_mean", "rs_var", "rs_count"} and len(blocks) == 2
    assert all(set(b) == {"ln_scale", "ln_shift", "w1", "b1", "w2", "b2"} for b in blocks)
    assert set(head) == {"ln_scale", "ln_shift", "w", "b"}
    # twin critics; the action joins at the input and is not normalised
    assert embed["w"].shape == (2, OBS + ACT, 32) and embed["rs_mean"].shape == (2, OBS)
    assert blocks[0]["w1"].shape == (2, 32, 128) and blocks[0]["w2"].shape == (2, 128, 32)
    assert embed["rs_count"].shape == (2,) and embed["rs_count"].dtype == jnp.float32
    actor = state.actor_params
    assert len(actor) == 3 and actor[1]["w1"].shape == (16, 64) and actor[-1]["w"].shape == (16, 2 * ACT)
    assert mlp.is_simba(actor) and not mlp.is_simba(init_train_state(_cfg(), OBS, ACT, 0).actor_params)
    # targets exist and hold copies of the statistics
    assert jax.tree.structure(state.target_critic_params) == jax.tree.structure(state.critic_params)
    text = jax.jit(make_learner_step(cfg, 1.0)).lower(state, _batch(np.random.default_rng(0))).as_text(debug_info=True)
    # (under vmap and jvp the words are wrapped, `jvp(critic)/vmap(lnorm)`:
    # trace.py reads the words, the next test the scopes they join to)
    for word in ("lnorm", "rsnorm", "critic/rsnorm/", "polyak/"):
        assert word in text, word


def test_the_scope_vocabulary_has_lnorm_and_rsnorm_under_both_nets():
    words = {"update/critic/lnorm", "update/critic/rsnorm", "update/actor/lnorm", "update/actor/rsnorm"}
    assert words <= set(trace.CHUNK_SCOPES)
    for word in ("lnorm", "rsnorm"):
        with trace.device_scope(word):
            pass
    learner = ShardedLearner(_cfg(**SOURCE), OBS, ACT, 1.0, 0.0, chunk_size=3)
    learner.run_chunk_async(jax.device_put(jnp.zeros((3, B, 2 * OBS + ACT + 3), jnp.float32), learner._chunk_sharding))
    scopes = set(learner.chunk_ops()["ops"].values())
    # what XLA:CPU leaves as an operation of its own, at least one of them
    assert scopes & words, scopes


PLAIN_FAMILIES = {
    "ddpg": dict(sac=False),
    "sac": dict(sac=True),
    "td3": dict(sac=False, twin_critic=True, policy_delay=2),
    "redq": dict(sac=True, critic_ensemble=5, target_subset=2, policy_delay=3),
    "crossq": dict(sac=True, crossq=True, policy_delay=3, adam_b1=0.5, action_insert_layer=0),
    "d4pg": dict(sac=False, distributional=True, n_step=3),
}


@pytest.mark.parametrize("family", sorted(PLAIN_FAMILIES))
def test_decay_and_entropy_scale_unset_are_every_older_programs_text(family):
    """`weight_decay` 0 and `target_entropy_scale` 1, spelt out or not, are
    one lowered program in every family that was here before; a decay is
    another. (That the text is also the PARENT COMMIT's was read once, on
    the chunk programs of all eight cells: CHANGES.md, PR 44.)"""
    kw = PLAIN_FAMILIES[family]
    plain, spelt, decayed = _cfg(**kw), _cfg(**kw, weight_decay=0.0, target_entropy_scale=1.0), _cfg(**kw, weight_decay=1e-2)
    state = init_train_state(plain, OBS, ACT, seed=0)
    batch = _batch(np.random.default_rng(1))
    texts = [jax.jit(make_learner_step(c, 1.0)).lower(state, batch).as_text() for c in (plain, spelt, decayed)]
    assert texts[0] == texts[1] and texts[0] != texts[2]


def test_adamw_decays_every_leaf_and_zero_is_adam():
    p = {"w": jnp.asarray([1.0, -2.0]), "ln_scale": jnp.asarray([1.0, 1.0])}
    g = {"w": jnp.asarray([0.5, 0.25]), "ln_scale": jnp.zeros(2)}
    opt = OptState(mu=jax.tree.map(jnp.zeros_like, p), nu=jax.tree.map(jnp.zeros_like, p), count=jnp.zeros((), jnp.int32))
    plain, _ = adam_update(p, g, opt, 1e-3)
    zero, _ = adam_update(p, g, opt, 1e-3, weight_decay=0.0)
    decayed, _ = adam_update(p, g, opt, 1e-3, weight_decay=0.1)
    for k in p:
        np.testing.assert_array_equal(plain[k], zero[k])
        # p - lr * (adam's step + decay * p): the decay's part is lr * decay * p
        np.testing.assert_allclose(np.asarray(plain[k]) - np.asarray(decayed[k]), 1e-3 * 0.1 * np.asarray(p[k]), rtol=2e-3)  # a float32 difference of values of size 1
    # a leaf with no gradient still decays: LayerNorm's scale shrinks
    assert np.all(np.asarray(decayed["ln_scale"]) < 1.0) and np.all(np.asarray(plain["ln_scale"]) == 1.0)


def test_polyak_copies_the_statistics_and_averages_the_rest(moved):
    online, target = moved.critic_params, init_train_state(_cfg(**SOURCE), OBS, ACT, seed=0).critic_params
    new = polyak_update(online, target, 0.25)
    for name in mlp.RS_STATS:
        np.testing.assert_array_equal(new[0][name], online[0][name])
        assert np.any(np.asarray(online[0][name]) != np.asarray(target[0][name]))
    np.testing.assert_allclose(new[1]["w1"], 0.25 * online[1]["w1"] + 0.75 * target[1]["w1"], rtol=1e-6)
    # a plain tree and a scalar are averaged as they were
    np.testing.assert_allclose(polyak_update(jnp.asarray(2.0), jnp.asarray(0.0), 0.25), 0.5)


def _want(state, obs, cfg):
    """The learner's own evaluation-mode head on `obs`."""
    mean, log_std = mlp.actor_gaussian_apply(state.actor_params, obs, cfg.sac_log_std_min, cfg.sac_log_std_max)
    return np.asarray(mean), np.asarray(log_std)


def test_the_layered_policy_is_the_evaluation_mode_actor(moved):
    """What leaves the learner: the input statistics folded into the
    embedding, the block and both LayerNorms as they are. The folded tree,
    the flat block, `NumpyPolicy` (the host workers' and the evaluator's) and
    the learner's own hand-off all give `actor_gaussian_apply` to 1e-5."""
    from distributed_ddpg_tpu.actors import policy as policy_lib

    # the worker's module never imports the learner's (it loads JAX): the two
    # constants it repeats are held equal here
    assert (policy_lib.LN_EPS, policy_lib.EXPANSION) == (mlp.LN_EPS, mlp.SIMBA_EXPANSION)
    cfg = _cfg(**SOURCE)
    actor = jax.device_get(moved.actor_params)
    assert float(np.abs(actor[0]["rs_mean"]).max()) > 1.0 and float(actor[0]["rs_count"]) == 4 * B
    folded = mlp.fold_norm(actor)
    assert set(folded[0]) == {"w", "b"} and set(folded[1]) == set(actor[1]) and set(folded[2]) == set(actor[2])
    obs = _batch(np.random.default_rng(9), 64).obs
    mean, log_std = _want(moved, obs, cfg)
    f_mean, f_log_std = mlp.actor_gaussian_apply(jax.tree.map(jnp.asarray, folded), obs, cfg.sac_log_std_min, cfg.sac_log_std_max)
    np.testing.assert_allclose(np.asarray(f_mean), mean, rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(f_log_std), log_std, rtol=0, atol=1e-5)
    # the flat block and its round trip
    flat = flatten_params(folded)
    assert is_layered(LAYOUT) and flat.size == layout_size(LAYOUT) == sum(x.size for layer in folded for x in layer.values())
    policy = NumpyPolicy(LAYOUT, 0.4, 0.1, gaussian=True)
    policy.load_flat(flat)
    np.testing.assert_array_equal(flatten_params(policy.tree()), flat)
    for ours, theirs in zip(policy.tree(), folded):
        assert set(ours) == set(theirs)
        for name in ours:
            np.testing.assert_array_equal(ours[name], theirs[name])
    head = policy.head(np.asarray(obs))
    np.testing.assert_allclose(head[:, :ACT], mean, rtol=0, atol=1e-5)
    # the evaluator acts deterministically on tanh(mean), as make_act_fn does
    act = make_act_fn(cfg, 0.4, 0.1)
    np.testing.assert_allclose(policy(np.asarray(obs)), np.asarray(act(moved.actor_params, obs)), rtol=0, atol=1e-5)
    # the workers sample the same tanh-Gaussian: their log_std is the head's
    sampler = NumpyPolicy(LAYOUT, 0.4, 0.1, gaussian=True, stochastic=True, seed=3)
    sampler.load_flat(flat)
    draws = np.stack([sampler(np.asarray(obs[:1])) for _ in range(400)])
    u = np.arctanh(np.clip((draws[:, 0] - 0.1) / 0.4, -0.999999, 0.999999))
    np.testing.assert_allclose(u.mean(0), mean[0], atol=4 * np.exp(log_std[0]).max() / 20)
    # the learner's own hand-off folds
    learner = ShardedLearner(cfg, OBS, ACT, 0.4, 0.1, chunk_size=2)
    learner.state = jax.device_put(moved, learner._state_sharding)
    np.testing.assert_array_equal(flatten_params(learner.actor_params_to_host()), flat)


def test_a_plain_nets_layout_and_block_are_the_parents():
    """Pairs (w_shape, b_shape), w then b, layer order, C order: written out
    by hand here as the parent wrote them."""
    layout = param_layout(OBS, ACT, (16, 8))
    assert layout == [((OBS, 16), (16,)), ((16, 8), (8,)), ((8, ACT), (ACT,))] and not is_layered(layout)
    params = jax.device_get(init_train_state(_cfg(sac=False, actor_hidden=(16, 8)), OBS, ACT, 0).actor_params)
    by_hand = np.concatenate([x for layer in params for x in (layer["w"].ravel(), layer["b"].ravel())])
    flat = flatten_params(params)
    assert flat.dtype == np.float32 and flat.tobytes() == by_hand.astype(np.float32).tobytes()
    assert layout_size(layout) == flat.size
    policy = NumpyPolicy(layout, 1.0)
    policy.load_flat(flat)
    x = np.random.default_rng(0).standard_normal((3, OBS)).astype(np.float32)
    h = np.maximum(np.maximum(x @ params[0]["w"] + params[0]["b"], 0) @ params[1]["w"] + params[1]["b"], 0)
    np.testing.assert_array_equal(policy(x), np.tanh(h @ params[2]["w"] + params[2]["b"]))


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_the_serving_engine_answers_with_the_layered_policy(moved, backend):
    """serve/server.py, both backends, on the flat block the pool
    broadcasts: head rows [mean | log_std] equal to `actor_gaussian_apply`
    to 1e-5, and the deterministic action its squash."""
    from distributed_ddpg_tpu.serve import InferenceServer

    cfg = _cfg(**SOURCE)
    flat = flatten_params(mlp.fold_norm(jax.device_get(moved.actor_params)))
    server = InferenceServer(
        LAYOUT, np.full(ACT, 0.4, np.float32), 0.1, max_batch=8, backend=backend, sac=True,
        log_std_min=cfg.sac_log_std_min, log_std_max=cfg.sac_log_std_max,
    )
    server.refresh(flat)
    obs = np.asarray(_batch(np.random.default_rng(5), 6).obs)
    mean, log_std = _want(moved, obs, cfg)
    heads = server._compute(obs)
    np.testing.assert_allclose(heads[:, :ACT], mean, rtol=0, atol=1e-5)
    np.testing.assert_allclose(heads[:, ACT:], log_std, rtol=0, atol=1e-5)
    action = server.sample(heads[0], tenant="t", request_id=1, explore=False)
    np.testing.assert_allclose(action, np.tanh(mean[0]) * 0.4 + 0.1, rtol=0, atol=1e-5)


def test_the_network_front_serves_the_layered_policy(moved):
    """serve/front: a published snapshot of the layered block behind the
    jax engine, over the wire: the action is the engine's own per-request
    draw (`sample`, keyed by seed, tenant and request) from the head that
    `actor_gaussian_apply` gives."""
    from distributed_ddpg_tpu.serve import InferenceServer
    from distributed_ddpg_tpu.serve.front import FrontClient, FrontServer

    cfg = _cfg(**SOURCE)
    flat = flatten_params(mlp.fold_norm(jax.device_get(moved.actor_params)))

    def engine(backend="jax"):
        return InferenceServer(LAYOUT, np.ones(ACT, np.float32), max_batch=4, backend=backend, sac=True, seed=5,
                               log_std_min=cfg.sac_log_std_min, log_std_max=cfg.sac_log_std_max)

    front = FrontServer(engine, http_port=None)
    front.publish("v1", flat)
    front.start()
    try:
        obs = np.asarray(_batch(np.random.default_rng(6), 1).obs[0])
        mean, log_std = _want(moved, obs[None], cfg)
        with FrontClient(front.port, tenant="alice") as cli:
            action, version = cli.act(obs, request_id=1)
        assert version == "v1"
        want = engine("numpy").sample(np.concatenate([mean[0], log_std[0]]), tenant="alice", request_id=1)
        np.testing.assert_allclose(action, want, rtol=0, atol=1e-5)
    finally:
        front.stop()


@pytest.mark.parametrize("model_axis", [1, 2])
def test_partition_rules_place_every_leaf(model_axis):
    """LayerNorm's and the normaliser's vectors and the residual stream's two
    ends replicate; inside a block w1 is column- and w2 row-sharded; the
    moments follow their parameters; and a chunk runs."""
    cfg = _cfg(**SOURCE, batch_size=8)
    state = init_train_state(cfg, OBS, ACT, seed=0)
    mesh = mesh_lib.make_mesh(8 // model_axis, model_axis)
    spec = mesh_lib.state_pspec(state, mesh)
    assert jax.tree.structure(spec, is_leaf=lambda x: isinstance(x, P)) == jax.tree.structure(
        jax.tree.map(lambda x: P(), state), is_leaf=lambda x: isinstance(x, P))
    for tree in (spec.actor_params, spec.critic_params, spec.target_critic_params, spec.critic_opt.mu):
        for layer in tree:
            for k in ("ln_scale", "ln_shift", "rs_mean", "rs_var", "rs_count", "w", "b", "b2"):
                assert all(axis is None for axis in layer.get(k, P()))
    block = spec.critic_params[1]
    if model_axis == 2:
        assert block["w1"] == P(None, None, "model") and block["b1"] == P(None, "model")
        assert block["w2"] == P(None, "model", None) and spec.actor_params[1]["w1"] == P(None, "model")
    else:
        assert all(axis is None for axis in block["w1"]) and all(axis is None for axis in block["w2"])
    learner = ShardedLearner(cfg.replace(model_axis=model_axis), OBS, ACT, 1.0, 0.0, chunk_size=2, mesh=mesh)
    packed = jnp.asarray(
        np.random.default_rng(5).standard_normal((2, learner.global_batch, 2 * OBS + ACT + 3)), jnp.float32)
    out = learner._chunk_step(learner.state, jax.device_put(packed, learner._chunk_sharding))
    assert int(out.state.step) == 2 and np.isfinite(float(out.metrics["resid_share"]))
    assert float(out.metrics["rsnorm_count"]) == 2 * learner.global_batch


def test_checkpoint_round_trip_with_the_statistics_and_the_count(tmp_path, moved):
    """Saved from a data mesh of 8 and restored under (4, 2): the bits are
    the saved ones, statistics and count with them, the chunk runs from
    there; a plain sac run refuses the checkpoint by name, and a checkpoint
    from before the field is no simba run's."""
    import os
    import shutil

    from distributed_ddpg_tpu import checkpoint as ckpt_lib

    cfg = _cfg(**SOURCE, batch_size=8)
    mesh1 = mesh_lib.make_mesh(8, 1)
    placed = jax.device_put(moved, mesh_lib.to_named(mesh1, mesh_lib.state_pspec(moved, mesh1)))
    ckpt_lib.save(str(tmp_path / "a"), 4, placed, config=cfg)
    template = init_train_state(cfg, OBS, ACT, seed=1)
    restored, at, _ = ckpt_lib.restore(str(tmp_path / "a"), template, config=cfg)
    assert at == 4 and jax.tree.structure(restored) == jax.tree.structure(moved)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(jax.device_get(moved))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(restored.actor_params[0]["rs_count"]) == 4 * B
    learner = ShardedLearner(cfg.replace(model_axis=2), OBS, ACT, 1.0, 0.0, chunk_size=2, mesh=mesh_lib.make_mesh(4, 2))
    learner.state = jax.device_put(restored, learner._state_sharding)
    packed = jnp.asarray(
        np.random.default_rng(5).standard_normal((2, learner.global_batch, 2 * OBS + ACT + 3)), jnp.float32)
    out = learner._chunk_step(learner.state, jax.device_put(packed, learner._chunk_sharding))
    assert int(out.state.step) == 6 and float(out.metrics["rsnorm_count"]) == 4 * B + 2 * learner.global_batch
    plain = _cfg(batch_size=8, action_insert_layer=0, actor_hidden=(16,))
    with pytest.raises(ValueError, match="simba: checkpoint=True run=False"):
        ckpt_lib.restore(str(tmp_path / "a"), init_train_state(plain, OBS, ACT, seed=1), config=plain)
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ckpt_fixtures", "parent_pr33")
    directory = str(tmp_path / "ckpt")
    shutil.copytree(src, directory)
    assert "simba" not in json.load(open(os.path.join(directory, "config_3.json")))
    writer = DDPGConfig(actor_hidden=(16, 16), critic_hidden=(16, 16), batch_size=8, seed=3)
    ckpt_lib.check_config_compatible(directory, 3, writer)
    with pytest.raises(ValueError, match="simba: checkpoint=False run=True"):
        ckpt_lib.check_config_compatible(
            directory, 3, writer.replace(sac=True, simba=True, action_insert_layer=0))


def test_the_forward_meter_reads_an_intervals_mean():
    from distributed_ddpg_tpu.metrics import ForwardMeter

    meter, counts = ForwardMeter(), [0.0] * 4
    assert meter.snapshot(counts) == {}
    counts[:] = [1e-3, 10, 3e-3, 10]  # two workers: 10 forwards each
    assert meter.snapshot(counts) == {"policy_forward_us": pytest.approx(200.0)}
    assert meter.snapshot(counts) == {}  # nothing since
    counts[0], counts[1] = 2e-3, 20
    assert meter.snapshot(counts) == {"policy_forward_us": pytest.approx(100.0)}


def test_train_runs_simba_end_to_end_and_its_records_say_so(tmp_path):
    """The normal path at a small size: host workers acting on the layered
    numpy policy, the device ring, run_sample_chunk on the scan leg, the
    refresh, the evaluator, a checkpoint and a resume; a plain sac run's
    records have none of the keys."""
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

    flags = ("--simba=true", "--actor_hidden=16", "--weight_decay=1e-2", "--sac_alpha=0.01",
             "--target_entropy_scale=0.5", "--action_insert_layer=0", "--eval_every=700", "--eval_episodes=1")
    cfg, summary, records = run("simba", *flags)
    assert summary["fused_chunk_active"] is False and summary["learner_steps"] >= 400
    header = next(r for r in records if r["kind"] == "header")
    final = next(r for r in records if r["kind"] == "final")
    facts = {"simba_actor_blocks": 1, "simba_actor_width": 16, "simba_critic_blocks": 2,
             "simba_critic_width": 32, "weight_decay": 0.01}
    for r in (header, final, summary):
        assert {k: r[k] for k in facts} == facts
    assert 0 < final["resid_share"] < 1 and np.isfinite(final["rsnorm_drift"])
    # the global batch's rows: 16 a replica on the virtual devices' data mesh
    assert final["rsnorm_count"] == summary["learner_steps"] * 16 * summary["mesh_data_axis"]
    trains = [r for r in records if r["kind"] == "train"]
    assert trains and all(r["policy_forward_us"] > 0 for r in trains if "policy_forward_us" in r)
    assert any("policy_forward_us" in r for r in trains)
    assert any(r["kind"] == "eval" and np.isfinite(r["eval_return"]) for r in records)
    assert summary["param_checksum"] != summary["param_checksum_start"]
    table = json.load(open(summary["chunk_ops_path"]))
    assert "update/polyak" in set(table["ops"].values())
    # and a second run resumes from it
    _, resumed, _ = run("simba", *flags, "--total_env_steps=2000")
    assert resumed["learner_steps"] > summary["learner_steps"]
    _, plain, plain_records = run("sac")
    for r in plain_records + [plain]:
        assert not {"resid_share", "rsnorm_count", "rsnorm_drift", "policy_forward_us", "simba_actor_blocks", "weight_decay"} & set(r)
