"""Device-actor subsystem (actors/device_pool.py; docs/DEVICE_ACTORS.md):
seed-fixed transition parity against a host-stepped JaxPendulum reference
loop, the devactor: fault grammar + bounded-restart supervisor contract,
config validation, the tier-1 train smoke (devactor_* in records, ZERO
transfer_ingest_items from the device source), and the tools.runs
digest."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ddpg_tpu.actors.device_pool import (
    DeviceActorError,
    DeviceActorPool,
    resolve_device_actor_chunk,
)
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.envs.jax_envs import JaxPendulum
from distributed_ddpg_tpu.faults import FaultPlan, InjectedFault
from distributed_ddpg_tpu.learner import init_train_state
from distributed_ddpg_tpu.models.mlp import actor_apply
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.replay.device import (
    DevicePrioritizedReplay,
    DeviceReplay,
)

E, K = 4, 6  # envs x scan steps for the unit-scale pool below


def _small_cfg(**kw):
    base = dict(
        env_id="Pendulum-v1",
        actor_backend="device",
        num_actors=0,
        device_actor_envs=E,
        device_actor_chunk=K,
        actor_hidden=(32, 32),
        critic_hidden=(32, 32),
        replay_capacity=4096,
    )
    base.update(kw)
    return DDPGConfig(**base)


def _one_device_mesh():
    return mesh_lib.make_mesh(data_axis=1, model_axis=1,
                              devices=jax.devices()[:1])


def _pool_with_params(cfg, mesh, fault=None):
    from jax.sharding import NamedSharding, PartitionSpec as P

    pool = DeviceActorPool(cfg, mesh=mesh, fault=fault)
    state = init_train_state(cfg, pool.obs_dim, pool.act_dim, cfg.seed)
    params = jax.device_put(
        state.actor_params,
        jax.tree.map(lambda _: NamedSharding(mesh, P()), state.actor_params),
    )
    pool.set_params(params)
    return pool, params


def test_chunk_resolution():
    assert resolve_device_actor_chunk(DDPGConfig(device_actor_chunk=5)) == 5
    assert resolve_device_actor_chunk(DDPGConfig()) == 8  # conftest pins cpu
    import distributed_ddpg_tpu.ops.fused_chunk as fc

    orig = fc.runs_native
    fc.runs_native = lambda: True
    try:
        assert resolve_device_actor_chunk(DDPGConfig()) == 64
    finally:
        fc.runs_native = orig


def test_device_actor_transition_parity_with_host_reference():
    """Seed-fixed parity: the rows the device pool landed in replay must
    match a HOST-stepped JaxPendulum reference loop that replays the
    rollout body's exact PRNG stream eagerly — obs / action / reward /
    boot_obs / discount all agree, so the compiled scan computes the same
    rollout a transparent per-step loop would."""
    cfg = _small_cfg()
    mesh = _one_device_mesh()
    pool, params = _pool_with_params(cfg, mesh)
    replay = DeviceReplay(cfg.replay_capacity, pool.obs_dim, pool.act_dim,
                          mesh=mesh, block_size=64, async_ship=False)
    assert pool.run_chunk(replay) == K * E
    landed = np.asarray(jax.device_get(replay.storage))[: K * E]

    # --- host reference: same key schedule, eager ops, no scan/jit ---
    env = JaxPendulum()
    params_host = jax.device_get(params)
    scale = pool.action_scale
    offset = pool.action_offset
    low = jnp.asarray(env.action_low)
    high = jnp.asarray(env.action_high)
    key = jax.random.PRNGKey(cfg.seed + 0xDA)
    k_init, key = jax.random.split(key)
    env_state = jax.vmap(env.init)(jax.random.split(k_init, E))
    obs = jax.vmap(env.observe)(env_state)
    ou = jnp.zeros((E, pool.act_dim), jnp.float32)
    expected = []
    for _ in range(K):
        key, k_ou, k_env, k_uni = jax.random.split(key, 4)
        ou = (
            ou
            + cfg.ou_theta * (0.0 - ou) * cfg.ou_dt
            + cfg.ou_sigma * jnp.sqrt(cfg.ou_dt)
            * jax.random.normal(k_ou, ou.shape, jnp.float32)
        )
        action = jnp.clip(
            actor_apply(params_host, obs, scale, offset) + ou * scale,
            low, high,
        )
        out = jax.vmap(env.step)(env_state, action,
                                 jax.random.split(k_env, E))
        discount = cfg.gamma * (
            1.0 - jnp.broadcast_to(out.terminated, (E,)).astype(jnp.float32)
        )
        expected.append(np.concatenate(
            [
                np.asarray(obs), np.asarray(action),
                np.asarray(out.reward)[:, None],
                np.asarray(discount)[:, None],
                np.asarray(out.boot_obs),
                np.ones((E, 1), np.float32),
            ],
            axis=-1,
        ))
        env_state, obs = out.state, out.obs
        ou = jnp.where(out.done[:, None], 0.0, ou)
    expected = np.concatenate(expected)  # [K*E, D], step-major
    np.testing.assert_allclose(landed, expected, rtol=1e-5, atol=1e-5)


def test_mountain_car_rows_at_the_goal_carry_zero_discount():
    """A built-in environment that truly TERMINATES, through the pool and
    into the ring: every environment planted just under the goal and moving
    fast ends on its first step, so the first E rows hold discount 0 and the
    PRE-reset next observation (past the goal), and the rows of the fresh
    episodes behind them hold gamma."""
    cfg = _small_cfg(env_id="MountainCarContinuous-v0")
    mesh = _one_device_mesh()
    pool, _ = _pool_with_params(cfg, mesh)
    env, carry = pool.env, pool._carry
    planted = carry.env_state._replace(
        pos=jnp.full_like(carry.env_state.pos, 0.449),
        vel=jnp.full_like(carry.env_state.vel, 0.07),
    )
    pool._carry = jax.device_put(
        carry._replace(env_state=planted, obs=jax.vmap(env.observe)(planted)),
        pool._carry_sharding,
    )
    replay = DeviceReplay(cfg.replay_capacity, pool.obs_dim, pool.act_dim,
                          mesh=mesh, block_size=64, async_ship=False)
    assert pool.run_chunk(replay) == K * E
    rows = np.asarray(jax.device_get(replay.storage))[: K * E]
    obs_dim, act_dim = pool.obs_dim, pool.act_dim
    discount = rows[:, obs_dim + act_dim + 1]
    next_pos = rows[:, obs_dim + act_dim + 2]
    np.testing.assert_allclose(rows[:E, 0], 0.449, atol=1e-6)
    assert np.all(discount[:E] == 0.0)
    assert np.all(next_pos[:E] >= env.goal_position)
    assert np.all(discount[E:] == np.float32(cfg.gamma))
    assert np.all(next_pos[E:] < env.goal_position)


def test_insert_device_rows_wraparound_and_per_stamp():
    """The donated device insert honors ring wraparound, and the PER
    subclass stamps landed rows with the running max priority (the
    every-transition-seen-once rule every other source follows)."""
    mesh = _one_device_mesh()
    per = DevicePrioritizedReplay(64, 3, 1, mesh=mesh, block_size=16,
                                  async_ship=False)
    width = per.width
    rows = jnp.arange(48 * width, dtype=jnp.float32).reshape(48, width)
    per.insert_device_rows(jax.device_put(rows))
    assert len(per) == 48
    prios = np.asarray(jax.device_get(per.priorities))
    assert (prios[:48] == 1.0).all() and (prios[48:] == 0.0).all()
    # Second insert wraps: 48 + 48 = 96 -> positions 48..63 then 0..31.
    per.insert_device_rows(jax.device_put(rows + 1000.0))
    assert len(per) == 64
    assert int(jax.device_get(per.ptr)) == 32
    storage = np.asarray(jax.device_get(per.storage))
    np.testing.assert_array_equal(
        storage[0], np.asarray(rows[16] + 1000.0)
    )
    assert (np.asarray(jax.device_get(per.priorities)) == 1.0).all()


def test_devactor_fault_grammar():
    plan = FaultPlan.parse("devactor:rollout:crash@2", seed=0)
    site = plan.site("devactor", "rollout")
    site.tick()
    with pytest.raises(InjectedFault):
        site.tick()
    # slow flavor parses with duration; bad kinds die at parse.
    FaultPlan.parse("devactor:rollout:slow@1~0.01", seed=0)
    with pytest.raises(ValueError, match="devactor"):
        DDPGConfig(faults="devactor:rollout:kill@1")


def test_devactor_bounded_restart_supervisor_contract():
    """A rollout-dispatch fault with the carry intact restarts bounded
    (counter devactor_restarts); past the budget the typed
    DeviceActorError surfaces."""
    cfg = _small_cfg()
    mesh = _one_device_mesh()
    plan = FaultPlan.parse("devactor:rollout:crash@1", seed=0)
    pool, _ = _pool_with_params(cfg, mesh,
                                fault=plan.site("devactor", "rollout"))
    replay = DeviceReplay(cfg.replay_capacity, pool.obs_dim, pool.act_dim,
                          mesh=mesh, block_size=64, async_ship=False)
    assert pool.run_chunk(replay) == K * E  # crash absorbed, rows landed
    assert pool.restarts == 1
    assert pool.snapshot()["devactor_restarts"] == 1

    # Budget exhaustion: every dispatch faults -> typed error, cause kept.
    plan = FaultPlan.parse(
        ";".join(f"devactor:rollout:crash@{i}" for i in range(1, 9)), seed=0
    )
    pool2, _ = _pool_with_params(cfg, mesh,
                                 fault=plan.site("devactor", "rollout"))
    with pytest.raises(DeviceActorError) as ei:
        pool2.run_chunk(replay)
    assert isinstance(ei.value.__cause__, InjectedFault)


def test_config_validation_rejects_unsupported_combos():
    with pytest.raises(ValueError, match="on-device \\(JAX\\)"):
        DDPGConfig(actor_backend="device", env_id="HalfCheetah-v4")
    with pytest.raises(ValueError, match="never call act\\(\\) on the host"):
        _small_cfg(serve_actors=True, num_actors=1)
    with pytest.raises(ValueError, match="jax_tpu"):
        DDPGConfig(actor_backend="device", backend="native")
    assert _small_cfg(n_step=3).n_step == 3  # PR 40: the pool folds n steps itself
    with pytest.raises(ValueError, match="host_replay"):
        _small_cfg(host_replay=True)
    with pytest.raises(ValueError, match="strict_sync"):
        _small_cfg(strict_sync=True, max_learn_ratio=1.0,
                   max_ingest_ratio=1.0)
    with pytest.raises(ValueError, match="num_actors"):
        DDPGConfig(num_actors=0)  # host backend needs workers
    with pytest.raises(ValueError, match="actor_backend"):
        DDPGConfig(actor_backend="gpu")
    # One rollout chunk may not exceed the ring: the scatter would write
    # duplicate positions in unspecified order (silent corruption).
    with pytest.raises(ValueError, match="replay_capacity"):
        _small_cfg(device_actor_envs=512, device_actor_chunk=16,
                   replay_capacity=4096)
    _small_cfg()  # the happy path constructs


def test_train_smoke_device_actors(tmp_path):
    """Tier-1 acceptance: a device-actor-only run trains, every record
    carries devactor_* fields, and the transfer scheduler's ingest class
    moved ZERO items — the device source never touches it."""
    from distributed_ddpg_tpu.train import train_jax

    cfg = _small_cfg(
        backend="jax_tpu",
        device_actor_envs=8,
        device_actor_chunk=4,
        total_env_steps=1600,
        replay_min_size=200,
        replay_capacity=20_000,
        eval_every=100_000,  # final eval only: keep the smoke fast
        log_path=str(tmp_path / "m.jsonl"),
    )
    out = train_jax(cfg)
    assert out["learner_steps"] > 0
    assert np.isfinite(out["final_return"])
    assert out["devactor_env_steps"] >= cfg.total_env_steps
    assert out["devactor_restarts"] == 0
    recs = [json.loads(l) for l in open(cfg.log_path)]
    finals = [r for r in recs if r["kind"] == "final"]
    assert finals and "devactor_rows_per_s" in finals[-1]
    assert "devactor_chunk_p95" in finals[-1]
    # Zero scheduler-ingest attributable to the device source: this run
    # has no host workers, so the class must never move an item.
    seen = [r["transfer_ingest_items"] for r in recs
            if "transfer_ingest_items" in r]
    assert seen and all(v == 0 for v in seen)
    # The rollout bracket rides PhaseTimers -> per-chunk step tails.
    assert any("t_devactor_ms" in r for r in recs)


def test_device_only_warmup_with_ingest_ratio_gate(tmp_path, one_chip):
    """Regression: with max_ingest_ratio armed and rows_per_chunk larger
    than min_fill, the device gate must still admit a chunk while any
    allowance remains (bounded one-chunk overshoot) — an all-or-nothing
    gate wedged warmup forever in a device-only run (no host workers to
    fill the buffer, learn_steps pinned at 0). On one device (conftest's
    `one_chip`): the gate is host arithmetic, and on the 8 virtual devices
    this run aborted in XLA:CPU's rendezvous about one time in fifteen
    beside five other workers, parent and change alike (PERF.md §7, 24)."""
    from distributed_ddpg_tpu.train import train_jax

    cfg = _small_cfg(
        backend="jax_tpu",
        device_actor_envs=32,
        device_actor_chunk=4,     # 128 rows/chunk > min_fill of 100
        total_env_steps=600,
        replay_min_size=100,
        replay_capacity=20_000,
        max_ingest_ratio=1.0,
        max_learn_ratio=1.0,
        eval_every=100_000,
        log_path=str(tmp_path / "m.jsonl"),
    )
    out = train_jax(cfg)
    assert out["learner_steps"] > 0
    assert out["devactor_env_steps"] >= cfg.total_env_steps


@pytest.mark.slow
def test_side_by_side_host_and_device_actors(tmp_path):
    """Both backends feeding the same ring: a tiny device pool (4 rows per
    chunk) plus one host worker — the run's total env steps exceed the
    device share, proving host rows kept flowing through the ingest
    pipeline while device rows took the donated insert."""
    from distributed_ddpg_tpu.train import train_jax

    cfg = _small_cfg(
        backend="jax_tpu",
        num_actors=1,
        device_actor_envs=2,
        device_actor_chunk=2,
        total_env_steps=2000,
        replay_min_size=200,
        replay_capacity=20_000,
        eval_every=100_000,
        log_path=str(tmp_path / "m.jsonl"),
    )
    out = train_jax(cfg)
    assert out["learner_steps"] > 0
    assert out["devactor_env_steps"] > 0
    recs = [json.loads(l) for l in open(cfg.log_path)]
    final = [r for r in recs if r["kind"] == "final"][-1]
    # final["step"] is host + device env steps; strictly more than the
    # device share means the host pool contributed real rows.
    assert final["step"] > out["devactor_env_steps"]


def test_tools_runs_devactor_digest(tmp_path):
    """tools.runs summarize/compare render the devactor digest."""
    from distributed_ddpg_tpu.tools.runs import compare_runs, render_summary, summarize_run

    path = tmp_path / "run.jsonl"
    recs = [
        {"kind": "train", "step": 100, "devactor_rows_per_s": 1000.0,
         "devactor_chunk_p95": 5.0, "devactor_env_steps": 100,
         "devactor_restarts": 0},
        {"kind": "final", "step": 200, "devactor_rows_per_s": 1200.0,
         "devactor_chunk_p95": 4.0, "devactor_env_steps": 200,
         "devactor_restarts": 0},
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    digest = summarize_run(str(path))
    assert digest["devactor"]["devactor_rows_per_s"]["last"] == 1200.0
    text = render_summary(digest)
    assert "device actors" in text and "devactor_rows_per_s" in text
    out, rows = compare_runs(str(path), str(path))
    assert any(r[0] == "devactor_rows_per_s" for r in rows)


# --------------------------------------------------------------------------
# rollout-state checkpointing (ISSUE-10 satellite; docs/DEVICE_ACTORS.md)
# --------------------------------------------------------------------------


def test_carry_state_roundtrip_continues_episodes():
    """carry_state_dict -> load_carry_state must resume the EXACT rollout
    stream: a restored pool's next chunk produces bit-identical rows to
    the uninterrupted pool's (env state, obs, OU state, and the PRNG key
    all ride the snapshot), and the episode accumulators carry over."""
    cfg = _small_cfg()
    mesh = _one_device_mesh()
    pool_a, params = _pool_with_params(cfg, mesh)
    rep_a = DeviceReplay(4096, pool_a.obs_dim, pool_a.act_dim, mesh=mesh,
                         block_size=64, async_ship=False)
    pool_a.run_chunk(rep_a)
    pool_a.run_chunk(rep_a)
    snap = pool_a.carry_state_dict()
    assert all(isinstance(v, np.ndarray) for v in snap.values())

    pool_b = DeviceActorPool(cfg, mesh=mesh)
    pool_b.set_params(params)
    assert pool_b.load_carry_state(snap) is True
    # Both pools now advance from the identical carry: next chunks match.
    rep_cont = DeviceReplay(4096, pool_a.obs_dim, pool_a.act_dim, mesh=mesh,
                            block_size=64, async_ship=False)
    rep_rest = DeviceReplay(4096, pool_a.obs_dim, pool_a.act_dim, mesh=mesh,
                            block_size=64, async_ship=False)
    pool_a.run_chunk(rep_cont)
    pool_b.run_chunk(rep_rest)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(rep_cont.storage)),
        np.asarray(jax.device_get(rep_rest.storage)),
    )
    # Device-side cumulative counters carried over (warmup gate input);
    # the host budget mirror deliberately did NOT (env_steps_offset owns
    # restored production).
    assert int(jax.device_get(pool_b._carry.steps)) == 3 * E * K
    assert pool_b.steps_done == E * K


def test_carry_state_mismatch_degrades_to_fresh():
    cfg = _small_cfg()
    mesh = _one_device_mesh()
    pool, _ = _pool_with_params(cfg, mesh)
    snap = pool.carry_state_dict()
    other = DeviceActorPool(_small_cfg(device_actor_envs=2 * E), mesh=mesh)
    assert other.load_carry_state(snap) is False  # shape mismatch: E differs
    assert other.load_carry_state({}) is False    # empty snapshot


def test_checkpoint_carries_devactor_sidecar(tmp_path):
    """checkpoint.save(devactor_state=...) writes devactor_carry.npz
    inside the step dir (manifest-covered), and restore() hands it back
    through meta_out — readable BEFORE the pool exists, the resume-order
    constraint train_jax lives under."""
    from distributed_ddpg_tpu import checkpoint as ckpt_lib

    cfg = _small_cfg()
    mesh = _one_device_mesh()
    pool, _ = _pool_with_params(cfg, mesh)
    rep = DeviceReplay(4096, pool.obs_dim, pool.act_dim, mesh=mesh,
                       block_size=64, async_ship=False)
    pool.run_chunk(rep)
    state = init_train_state(cfg, pool.obs_dim, pool.act_dim, cfg.seed)
    d = str(tmp_path)
    ckpt_lib.save(d, 7, state, rep, cfg, env_steps=E * K,
                  devactor_state=pool.carry_state_dict())
    import os

    assert os.path.exists(os.path.join(d, "step_7", "devactor_carry.npz"))
    ok, why = ckpt_lib.verify_checkpoint(d, 7)
    assert ok, why
    meta = {}
    _, step, env_steps = ckpt_lib.restore(d, state, rep, meta_out=meta)
    assert step == 7 and env_steps == E * K
    assert "devactor_carry" in meta
    fresh = DeviceActorPool(cfg, mesh=mesh)
    assert fresh.load_carry_state(meta["devactor_carry"]) is True
    assert int(jax.device_get(fresh._carry.steps)) == E * K


@pytest.mark.slow
def test_train_resume_restores_rollout_state(tmp_path):
    """End-to-end satellite acceptance: a checkpointed device-actor run
    resumed from disk continues its episodes — the restored carry's step
    counter is live in the resumed pool instead of E fresh resets.
    Slow-marked (two full train_jax runs); the tier-1 carry tests above
    pin the same contract at unit scale."""
    from distributed_ddpg_tpu import checkpoint as ckpt_lib
    from distributed_ddpg_tpu.train import train_jax

    ckpt_dir = str(tmp_path / "ckpt")
    cfg = _small_cfg(
        backend="jax_tpu",
        device_actor_envs=8,
        device_actor_chunk=4,
        total_env_steps=1200,
        replay_min_size=200,
        replay_capacity=20_000,
        eval_every=100_000,
        checkpoint_dir=ckpt_dir,
        checkpoint_every=8,
        log_path=str(tmp_path / "a.jsonl"),
    )
    out = train_jax(cfg)
    assert out["learner_steps"] > 0
    step = ckpt_lib.latest_step(ckpt_dir)
    assert step is not None
    import os

    assert os.path.exists(
        os.path.join(ckpt_dir, f"step_{step}", "devactor_carry.npz")
    )
    # Resume with a larger budget: the restored pool must keep counting
    # from the checkpointed carry (its warmup gate stays closed) and the
    # run must complete cleanly.
    out2 = train_jax(cfg.replace(
        total_env_steps=2 * cfg.total_env_steps,
        log_path=str(tmp_path / "b.jsonl"),
    ))
    assert out2["learner_steps"] >= out["learner_steps"]
    assert out2["devactor_restarts"] == 0
