"""SAC (arXiv 1801.01290/1812.05905; third beyond-parity family): stochastic
tanh-Gaussian actor with reparameterized sampling, twin critics (TD3's
stacked-leading-axis machinery), entropy-regularized Bellman targets, and a
learned temperature driving policy entropy toward -act_dim."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.learner import (
    init_train_state,
    jit_learner_step,
    make_act_fn,
)
from distributed_ddpg_tpu.ops import losses
from distributed_ddpg_tpu.types import Batch

OBS, ACT, B = 5, 2, 16


def _cfg(**kw):
    base = dict(
        actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=B,
        sac=True, seed=0,
    )
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


def test_sac_init_shapes():
    s = init_train_state(_cfg(), OBS, ACT, seed=0)
    # Gaussian head: final layer emits [mean | log_std] (2 * act_dim).
    assert s.actor_params[-1]["w"].shape[-1] == 2 * ACT
    # Twin critics: stacked leading axis, independent inits.
    for layer in s.critic_params:
        assert layer["w"].shape[0] == 2 and layer["w"].ndim == 3
        assert not np.allclose(layer["w"][0], layer["w"][1])
    # Temperature scalar + its own Adam state.
    assert np.isclose(float(s.log_alpha), np.log(0.2))
    assert int(s.alpha_opt.count) == 0
    # Non-SAC states keep None (empty pytree node) there.
    s2 = init_train_state(
        DDPGConfig(actor_hidden=(32,), critic_hidden=(32, 32)), OBS, ACT, seed=0
    )
    assert s2.log_alpha is None and s2.alpha_opt is None


def test_sac_log_prob_matches_torch_oracle():
    """sac_sample's log-density must equal an independent implementation:
    torch.distributions Normal -> tanh -> affine(scale, offset) via
    TransformedDistribution, evaluated at the same sampled actions."""
    torch = pytest.importorskip("torch")

    rng = np.random.default_rng(0)
    mean = rng.standard_normal((B, ACT)).astype(np.float32)
    log_std = rng.uniform(-2.0, 0.5, (B, ACT)).astype(np.float32)
    scale, offset = 1.7, 0.3
    action, lp = losses.sac_sample(
        jnp.asarray(mean), jnp.asarray(log_std),
        jax.random.normal(jax.random.PRNGKey(1), mean.shape), scale, offset,
    )
    dist = torch.distributions.TransformedDistribution(
        torch.distributions.Normal(
            torch.tensor(mean), torch.tensor(np.exp(log_std))
        ),
        [
            torch.distributions.transforms.TanhTransform(),
            torch.distributions.transforms.AffineTransform(offset, scale),
        ],
    )
    # Independent=sum over action dims.
    dist = torch.distributions.Independent(dist, 1)
    # Clip fractionally inside the box: atanh((a-offset)/scale) must stay
    # finite in the torch oracle (our jax path never inverts).
    a = np.clip(np.asarray(action), offset - scale + 1e-5, offset + scale - 1e-5)
    lp_torch = dist.log_prob(torch.tensor(a)).numpy()
    np.testing.assert_allclose(np.asarray(lp), lp_torch, rtol=1e-3, atol=1e-3)


def test_sac_entropy_target_in_env_units():
    """The -log(scale) Jacobian term: scaling the action box must shift
    log-probs by -sum(log scale) exactly (density lives in env units)."""
    rng = np.random.default_rng(2)
    mean = jnp.asarray(rng.standard_normal((B, ACT)), jnp.float32)
    log_std = jnp.asarray(rng.uniform(-1, 0, (B, ACT)), jnp.float32)
    eps = jax.random.normal(jax.random.PRNGKey(3), mean.shape)
    _, lp1 = losses.sac_sample(mean, log_std, eps, 1.0)
    _, lp4 = losses.sac_sample(mean, log_std, eps, 4.0)
    # Exact up to the _TANH_EPS regularizer inside log(scale*(1-t^2)+eps).
    np.testing.assert_allclose(
        np.asarray(lp4), np.asarray(lp1) - ACT * np.log(4.0), atol=1e-4
    )


def test_sac_min_over_ensemble_target():
    """Bias target-critic member 1 far above member 0: the entropy-
    regularized target must track member 0 (the min)."""
    cfg = _cfg()
    s = init_train_state(cfg, OBS, ACT, seed=0)
    biased = list(dict(l) for l in s.critic_params)
    last = dict(biased[-1])
    last["b"] = jnp.asarray(s.critic_params[-1]["b"]).at[1].add(100.0)
    biased[-1] = last
    target_critic = tuple(biased)

    batch = _batch(np.random.default_rng(0))
    eps = jax.random.normal(jax.random.PRNGKey(0), (B, ACT))
    alpha = 0.2
    _, td = losses.sac_critic_loss(
        s.critic_params, s.actor_params, target_critic, batch,
        1.0, eps, alpha, cfg.sac_log_std_min, cfg.sac_log_std_max,
    )
    from distributed_ddpg_tpu.models.mlp import (
        actor_gaussian_apply,
        critic_apply,
    )

    mean, log_std = actor_gaussian_apply(
        s.actor_params, batch.next_obs, cfg.sac_log_std_min, cfg.sac_log_std_max
    )
    na, nlp = losses.sac_sample(mean, log_std, eps, 1.0)
    q0 = critic_apply(
        jax.tree.map(lambda x: x[0], target_critic), batch.next_obs, na, 1
    )
    y = batch.reward + batch.discount * (q0 - alpha * nlp)
    q_on = jnp.stack([
        critic_apply(
            jax.tree.map(lambda x: x[i], s.critic_params),
            batch.obs, batch.action, 1,
        )
        for i in (0, 1)
    ])
    expect_td = y[None] - q_on
    np.testing.assert_allclose(
        np.asarray(td), np.asarray(expect_td.mean(0)), rtol=1e-5, atol=1e-6
    )


def test_sac_alpha_autotune_direction_and_determinism():
    """One step must move log_alpha opposite the sign of
    (E[log pi] + target_H) — the exact gradient of the linear temperature
    objective — and the fold_in(seed, step) stream must make the step
    replayable bit-for-bit."""
    cfg = _cfg()
    s = init_train_state(cfg, OBS, ACT, seed=0)
    batch = _batch(np.random.default_rng(1))
    step = jit_learner_step(cfg, 1.0, donate=False)

    # Recompute the actor aux exactly as the step will: same folded key.
    key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed ^ 0x5AC0), s.step)
    _, k_cur = jax.random.split(key)
    _, mean_lp = losses.sac_actor_loss(
        s.actor_params, s.critic_params, batch, 1.0,
        jax.random.normal(k_cur, (B, ACT)),
        float(jnp.exp(s.log_alpha)), cfg.sac_log_std_min, cfg.sac_log_std_max,
    )
    tgt_h = -float(ACT)
    out1 = step(s, batch)
    out2 = step(s, batch)
    np.testing.assert_array_equal(
        np.asarray(out1.td_errors), np.asarray(out2.td_errors)
    )
    delta = float(out1.state.log_alpha) - float(s.log_alpha)
    # grad = -(mean_lp + tgt_h); Adam's first step moves against the grad.
    expected_sign = np.sign(float(mean_lp) + tgt_h)
    assert np.sign(delta) == expected_sign and delta != 0.0
    assert int(out1.state.alpha_opt.count) == 1
    # Fixed-alpha mode: log_alpha frozen, no alpha opt state.
    cfg_fixed = _cfg(sac_autotune=False)
    s_f = init_train_state(cfg_fixed, OBS, ACT, seed=0)
    out_f = jit_learner_step(cfg_fixed, 1.0, donate=False)(s_f, batch)
    assert float(out_f.state.log_alpha) == float(s_f.log_alpha)
    assert out_f.state.alpha_opt is None


def test_sac_numpy_policy_parity_and_sampling():
    """Worker-side numpy Gaussian policy: deterministic mode must match the
    jitted eval act fn bit-close; stochastic mode must actually spread."""
    from distributed_ddpg_tpu.actors.policy import (
        NumpyPolicy,
        actor_head_dim,
        flatten_params,
        param_layout,
    )

    cfg = _cfg()
    s = init_train_state(cfg, OBS, ACT, seed=0)
    layout = param_layout(OBS, actor_head_dim(ACT, True), (32, 32))
    flat = flatten_params(s.actor_params)
    det = NumpyPolicy(layout, 1.3, 0.1, gaussian=True)
    det.load_flat(flat)
    obs = np.random.default_rng(5).standard_normal((4, OBS)).astype(np.float32)
    act_fn = make_act_fn(cfg, 1.3, action_offset=0.1)
    np.testing.assert_allclose(
        det(obs), np.asarray(act_fn(s.actor_params, obs)), rtol=1e-5, atol=1e-5
    )
    sto = NumpyPolicy(layout, 1.3, 0.1, gaussian=True, stochastic=True, seed=7)
    sto.load_flat(flat)
    draws = np.stack([sto(obs[:1])[0] for _ in range(64)])
    assert draws.std(axis=0).min() > 1e-3  # actually stochastic
    assert np.all(np.abs(draws - 0.1) <= 1.3 + 1e-6)  # inside the box


def test_sac_warmup_uniform_resolution_and_acting():
    """warmup_uniform_steps: -1 auto-resolves to replay_min_size for SAC
    (its Gaussian exploration needs broad seed data — without it Pendulum
    sticks at ~-1100; with it, solved) and 0 for OU families; during
    warmup the agent's explore actions are uniform over the box."""
    from distributed_ddpg_tpu.agent import DDPGAgent
    from distributed_ddpg_tpu.envs import make, spec_of

    assert _cfg(replay_min_size=777).resolved_warmup_uniform() == 777
    assert DDPGConfig(replay_min_size=777).resolved_warmup_uniform() == 0
    assert _cfg(warmup_uniform_steps=5).resolved_warmup_uniform() == 5
    assert _cfg(warmup_uniform_steps=0).resolved_warmup_uniform() == 0
    with pytest.raises(ValueError, match="warmup_uniform_steps"):
        DDPGConfig(warmup_uniform_steps=-2)
    # A throttle at/above the pool's heartbeat timeout would respawn-loop
    # every worker (the sleep sits between heartbeat stamps).
    from distributed_ddpg_tpu.actors.pool import ActorPool as _AP
    from distributed_ddpg_tpu.envs import make as _make, spec_of as _spec_of

    _s = _spec_of(_make("Pendulum-v1", seed=0, prefer_builtin=True))
    with pytest.raises(ValueError, match="heartbeat"):
        _AP(DDPGConfig(actor_throttle_s=35.0), _s, heartbeat_timeout=30.0)

    cfg = _cfg(
        env_id="Pendulum-v1", replay_min_size=200, warmup_uniform_steps=200,
        actor_hidden=(16,), critic_hidden=(16, 16),
    )
    env = make(cfg.env_id, seed=0, prefer_builtin=True)
    spec = spec_of(env)
    agent = DDPGAgent(cfg, spec)
    obs, _ = env.reset(seed=0)
    draws = []
    for _ in range(200):
        a = agent.act(obs, explore=True)
        draws.append(a)
        agent.observe(obs, a, 0.0, False, obs)
    draws = np.stack(draws)
    # Uniform draws reach near the box edge; the init policy (std~0.22
    # pre-tanh around mean 0) essentially never does.
    assert np.abs(draws).max() > 0.95 * spec.action_high[0]
    assert np.abs(np.mean(draws)) < 0.5  # centered
    # Past the warmup budget, acting switches to the (narrow) policy.
    post = np.stack([agent.act(obs, explore=True) for _ in range(50)])
    assert np.abs(post).max() < 0.95 * spec.action_high[0]

    # Pool-side budget: resume progress and drained steps consume it, so a
    # respawned/resumed worker never re-injects random actions (ceil-split
    # across workers while any budget remains).
    from distributed_ddpg_tpu.actors.pool import ActorPool

    pool = ActorPool(_cfg(replay_min_size=1000, num_actors=4), spec)
    try:
        assert pool.warmup_budget_per_worker() == 250
        pool.env_steps_offset = 900
        assert pool.warmup_budget_per_worker() == 25
        pool._steps_received = 200
        assert pool.warmup_budget_per_worker() == 0
    finally:
        pool.stop()

    # target_entropy: nan = auto; an explicit 0.0 is a real target and
    # must NOT be remapped.
    import math

    assert math.isnan(DDPGConfig(sac=True).target_entropy)
    assert DDPGConfig(sac=True, target_entropy=0.0).target_entropy == 0.0


def test_sac_config_gates():
    with pytest.raises(ValueError, match="family"):
        DDPGConfig(sac=True, twin_critic=True)
    with pytest.raises(ValueError, match="family"):
        DDPGConfig(sac=True, distributional=True)
    with pytest.raises(ValueError, match="backend"):
        DDPGConfig(sac=True, backend="native")
    with pytest.raises(ValueError, match="sac_alpha"):
        DDPGConfig(sac=True, sac_alpha=0.0)
    with pytest.raises(ValueError, match="log_std"):
        DDPGConfig(sac=True, sac_log_std_min=3.0)
    from distributed_ddpg_tpu.ops import fused_chunk

    # SAC is inside the megakernel envelope since round 4
    # (tests/test_fused_chunk.py SAC parity cases).
    assert fused_chunk.supported(_cfg())


def test_sac_sharded_learner_on_mesh():
    """The Gaussian head + twin ensemble + temperature scalar must flow
    through the mesh pspec trees (log_alpha replicates), device-replay
    sampling, and donation on the 8-device CPU mesh."""
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.replay.device import DeviceReplay
    from distributed_ddpg_tpu.types import pack_batch_np

    cfg = _cfg(batch_size=8)
    mesh = mesh_lib.make_mesh(data_axis=4, model_axis=2, devices=jax.devices())
    lrn = ShardedLearner(cfg, OBS, ACT, action_scale=1.0, mesh=mesh, chunk_size=4)
    assert not lrn.fused_chunk_active  # SAC -> scan path
    rng = np.random.default_rng(3)
    n = 256
    dr = DeviceReplay(1024, OBS, ACT, mesh=lrn.mesh, block_size=128)
    dr.add_packed(
        pack_batch_np(
            {
                "obs": rng.standard_normal((n, OBS)).astype(np.float32),
                "action": rng.uniform(-1, 1, (n, ACT)).astype(np.float32),
                "reward": rng.standard_normal(n).astype(np.float32),
                "discount": np.full(n, 0.99, np.float32),
                "next_obs": rng.standard_normal((n, OBS)).astype(np.float32),
            }
        )
    )
    out = lrn.run_sample_chunk(dr)
    assert np.isfinite(float(out.metrics["critic_loss"]))
    out2 = lrn.run_sample_chunk(dr)
    assert np.isfinite(float(out2.metrics["critic_loss"]))
    # Temperature advanced once per learner step, replicated (scalar).
    assert int(jax.device_get(lrn.state.alpha_opt.count)) == 8
    assert np.asarray(jax.device_get(lrn.state.log_alpha)).ndim == 0


def test_sac_checkpoint_roundtrip(tmp_path):
    """log_alpha/alpha_opt must survive save->restore (None-defaulted
    TrainState fields change the SAC tree, not the other families')."""
    from distributed_ddpg_tpu import checkpoint as ckpt_lib
    from distributed_ddpg_tpu.replay import make_replay

    cfg = _cfg(checkpoint_dir=str(tmp_path / "ckpt"))
    s = init_train_state(cfg, OBS, ACT, seed=0)
    step = jit_learner_step(cfg, 1.0, donate=False)
    batch = _batch(np.random.default_rng(4))
    for _ in range(3):
        s = step(s, batch).state
    replay = make_replay(cfg, OBS, ACT)
    rng = np.random.default_rng(6)
    for _ in range(8):
        replay.add(
            rng.standard_normal((1, OBS)).astype(np.float32),
            rng.uniform(-1, 1, (1, ACT)).astype(np.float32),
            np.asarray([0.5], np.float32),
            np.asarray([0.99], np.float32),
            rng.standard_normal((1, OBS)).astype(np.float32),
        )
    ckpt_lib.save(cfg.checkpoint_dir, 3, s, replay, cfg, env_steps=30)
    template = init_train_state(cfg, OBS, ACT, seed=1)
    restored, rstep, renv = ckpt_lib.restore(
        cfg.checkpoint_dir, template, make_replay(cfg, OBS, ACT), config=cfg
    )
    assert rstep == 3
    np.testing.assert_array_equal(
        np.asarray(restored.log_alpha), np.asarray(s.log_alpha)
    )
    np.testing.assert_array_equal(
        np.asarray(restored.alpha_opt.mu), np.asarray(s.alpha_opt.mu)
    )


@pytest.mark.slow
def test_sac_train_jax_end_to_end(tmp_path):
    from distributed_ddpg_tpu.train import train_jax

    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 32), num_actors=2,
        sac=True, actor_lr=3e-4, critic_lr=3e-4,
        total_env_steps=4_000, replay_min_size=500, replay_capacity=20_000,
        eval_every=0, max_ingest_ratio=50.0,
        log_path=str(tmp_path / "m.jsonl"),
    )
    out = train_jax(cfg)
    assert out["learner_steps"] >= 40
    assert np.isfinite(out["final_return"])
