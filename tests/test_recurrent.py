"""What recurrent TD3's deployment added outside the update
(models/recurrent.py, ops/exploration.py, actors/device_pool.py, envs/
jax_envs.py, config.py, types.py), at small sizes on the CPU: the rollout's one
step against the learner's whole window, the window fold against a plain
Python fold over recorded steps, one row an env step, the carry with its
policy state through a save and a restore, the occluded stand-in, and what
`_check_recurrent` refuses. The update itself is tests/test_reference_rtd3.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ddpg_tpu.actors.device_pool import DeviceActorPool
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.envs import jax_envs
from distributed_ddpg_tpu.envs.jax_envs import (
    OCCLUDED_STAND_IN_ID, STAND_IN_ID, IsaacHumanoidStandIn, OccludedHumanoidStandIn, make_jax_env,
)
from distributed_ddpg_tpu.envs.registry import DEVICE_ONLY, make, spec_of
from distributed_ddpg_tpu.learner import init_train_state, make_act_fn
from distributed_ddpg_tpu.models import recurrent as recnet
from distributed_ddpg_tpu.ops.exploration import seq_fold, seq_window
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.partition import net_pspec
from distributed_ddpg_tpu.replay.device import DeviceReplay
from distributed_ddpg_tpu.types import ObsSpec, packed_width, unpack_windows

E, L = 4, 8
OBS, ACT = OccludedHumanoidStandIn.obs_dim, OccludedHumanoidStandIn.act_dim


def cfg(**kw):
    base = dict(
        backend="jax_tpu", env_id=OCCLUDED_STAND_IN_ID, recurrent=True, twin_critic=True, action_insert_layer=0,
        actor_backend="device", num_actors=0, device_actor_envs=E, device_actor_chunk=1, exploration="gaussian",
        explore_sigma_min=0.1, explore_sigma_max=0.1, seq_len=L, rnn_hidden=16, obs_embed=8, action_embed=4,
        reward_embed=4, actor_hidden=(16, 16), critic_hidden=(16, 16), batch_size=4, target_noise=0.2,
        target_noise_clip=0.5, replay_capacity=512, seed=5,
    )
    base.update(kw)
    return DDPGConfig(**base)


def one_device_mesh():
    return mesh_lib.make_mesh(data_axis=1, model_axis=1, devices=jax.devices()[:1])


def pool_and_ring(config, mesh):
    pool = DeviceActorPool(config, mesh=mesh)
    state = init_train_state(config, pool.obs, ACT, config.seed)
    pool.set_params(state.actor_params)
    ring = DeviceReplay(config.replay_capacity, pool.obs, ACT, mesh=mesh, block_size=16, async_ship=False)
    return pool, ring, state


class Brief(OccludedHumanoidStandIn):
    """Episodes of at most 5 steps, some ended sooner by termination: both
    kinds of end fall inside a window of 8."""

    max_episode_steps = 5
    BOX = 0.16


def test_one_step_policy_stepped_over_a_window_is_the_learners_forward():
    """Actor and learner are one function: `actor_step` from a zero memory,
    fed each step's previous action and reward, gives at step t what
    `memory` + `actor_head` give at slot t of the whole window."""
    config = cfg()
    actor = init_train_state(config, ObsSpec((OBS,), steps=L), ACT, 3).actor_params
    rng = np.random.default_rng(0)
    obs = jnp.asarray(rng.normal(size=(E, L + 1, OBS)), jnp.float32)
    action = jnp.asarray(rng.uniform(-1, 1, (E, L, ACT)), jnp.float32)
    reward = jnp.asarray(rng.normal(size=(E, L)), jnp.float32)
    prev_a = jnp.concatenate([jnp.zeros((E, 1, ACT)), action], axis=1)
    prev_r = jnp.concatenate([jnp.zeros((E, 1)), reward], axis=1)
    h = recnet.memory(actor, obs, prev_a, prev_r)
    whole = recnet.actor_head(actor, h, obs, 1.0, 0.0)
    act = make_act_fn(config, 1.0, 0.0)
    mem = recnet.zero_memory(E, config.rnn_hidden, ACT)
    for t in range(L + 1):
        stepped, (hh, cc) = act(actor, obs[:, t], mem)
        np.testing.assert_allclose(stepped, whole[:, t], rtol=1e-4, atol=1e-7)
        np.testing.assert_allclose(hh, h[:, t], atol=2e-6)
        if t < L:
            mem = recnet.Memory(h=hh, c=cc, prev_action=action[:, t], prev_reward=reward[:, t])
    assert float(jnp.max(jnp.abs(h[:, -1] - h[:, 0]))) > 0.05  # a memory that moves


def python_fold(steps):
    """The plain fold over one environment's recorded steps: a list of the
    episode's steps so far, its last L as the row, emptied where the episode
    ended."""
    episode, rows = [], []
    for s in steps:
        episode.append(s)
        last = episode[-L:]
        n = len(last)
        obs = np.zeros((L + 1, OBS), np.float32)
        action, scalars = np.zeros((L, ACT), np.float32), np.zeros((3, L), np.float32)
        for j, step in enumerate(last):
            obs[j], obs[j + 1] = step["obs"], step["boot_obs"]
            action[j] = step["action"]
            scalars[:, j] = step["reward"], float(step["terminated"]), 1.0
        rows.append(np.concatenate([obs.ravel(), action.ravel(), scalars.ravel()]))
        if s["done"]:
            episode = []
    return np.stack(rows)


def test_seq_fold_against_a_plain_python_fold(monkeypatch):
    """The pool's rows, an env at a time, against the Python fold over the
    steps the same pool took (its 1-step rows are what `vector_env_step`
    packs; here they are read back off the windows' newest slots): mask,
    left alignment, the bootstrap observation in o_{t+1}, ends by termination
    and by truncation inside a window, and the memory zeroed at each."""
    monkeypatch.setitem(jax_envs._JAX_ENVS, OCCLUDED_STAND_IN_ID, Brief)
    config, mesh, steps = cfg(), one_device_mesh(), 30
    pool, ring, _ = pool_and_ring(config, mesh)
    recorded = [[] for _ in range(E)]
    resets = 0
    for t in range(steps):
        before = jax.device_get(pool._carry)
        assert pool.run_chunk(ring) == E  # one row an env step
        after = jax.device_get(pool._carry)
        row = np.asarray(jax.device_get(ring.storage))[t * E : (t + 1) * E]
        b = unpack_windows(row, OBS, ACT, L)
        for e in range(E):
            n = int(b.mask[e].sum())
            done = int(after.env_state.t[e]) == 0  # the environment began a new episode
            assert (int(after.seq.count[e]) == 0) == done
            recorded[e].append(dict(
                obs=before.obs[e], action=b.action[e, n - 1], reward=b.reward[e, n - 1],
                terminated=bool(b.terminated[e, n - 1]), boot_obs=b.obs[e, n], done=done,
            ))
            np.testing.assert_array_equal(b.obs[e, n - 1], before.obs[e])  # o_t is what the policy saw
            if done:
                # the memory, the previous action and reward start again at zero
                assert not after.memory.h[e].any() and not after.memory.c[e].any()
                assert not after.memory.prev_action[e].any() and after.memory.prev_reward[e] == 0.0
                np.testing.assert_array_equal(after.seq.obs[e, 0], after.obs[e])
                if not recorded[e][-1]["terminated"]:
                    # a truncated episode's row bootstraps from the pre-reset observation
                    assert not np.array_equal(b.obs[e, n], after.obs[e])
            else:
                np.testing.assert_array_equal(after.memory.prev_action[e], b.action[e, n - 1])
                assert after.memory.prev_reward[e] == b.reward[e, n - 1] and after.memory.h[e].any()
                np.testing.assert_array_equal(b.obs[e, n], after.obs[e])
            resets += done
    landed = np.asarray(jax.device_get(ring.storage))[: steps * E].reshape(steps, E, -1)
    ends = {"terminated": 0, "truncated": 0}
    for e in range(E):
        np.testing.assert_array_equal(landed[:, e], python_fold(recorded[e]))
        for s in recorded[e]:
            ends["terminated" if s["terminated"] else "truncated"] += s["done"]
    assert ends["terminated"] > 0 and ends["truncated"] > 0, ends
    assert pool.snapshot()["policy_state_resets"] == resets == sum(ends.values())
    assert len(ring) == steps * E and pool.steps_done == steps * E and pool.pending_rows == 0


def test_a_window_slides_once_an_episode_is_older_than_it():
    """Pure fold, no pool: an episode of L + 3 steps; from step L on the row
    is the LAST L steps, full mask, and the oldest has left."""
    window = seq_window(jnp.zeros((1, 2)), L, 1)

    class Out:
        pass

    for t in range(L + 3):
        out = Out()
        out.boot_obs = out.obs = jnp.full((1, 2), float(t + 1))
        out.reward, out.done = jnp.full((1,), float(t)), jnp.zeros((1,), bool)
        out.terminated = jnp.zeros((1,), bool)
        window, rows = seq_fold(window, jnp.full((1, 1), float(t)), out)
        b = unpack_windows(rows, 2, 1, L)
        n = min(t + 1, L)
        assert float(b.mask.sum()) == n and int(window.count[0]) == n
        np.testing.assert_array_equal(b.reward[0, :n], np.arange(t + 1 - n, t + 1))
        np.testing.assert_array_equal(b.obs[0, : n + 1, 0], np.arange(t + 1 - n, t + 2))
        assert not np.asarray(b.obs[0, n + 1 :]).any() and not np.asarray(b.action[0, n:]).any()


def test_carry_with_its_policy_state_restores_and_continues_bit_for_bit():
    mesh = one_device_mesh()
    a, ring_a, _ = pool_and_ring(cfg(), mesh)
    for _ in range(5):
        a.run_chunk(ring_a)  # mid-episode: the windows hold five steps, the memories are warm
    saved = a.carry_state_dict()
    plain = DeviceActorPool(cfg(recurrent=False, env_id=STAND_IN_ID), mesh=mesh)
    # (h, c, previous action and reward), the window's five leaves, the resets' count
    assert len(saved) == len(jax.tree.leaves(a._carry)) == len(jax.tree.leaves(plain._carry)) + 4 + 5 + 1
    b, ring_b, _ = pool_and_ring(cfg(), mesh)
    assert b.load_carry_state(saved)
    assert jax.device_get(b._carry.memory.h).any() and int(jax.device_get(b._carry.seq.count)[0]) == 5
    for _ in range(4):
        a.run_chunk(ring_a)
        b.run_chunk(ring_b)
    for x, y in zip(jax.tree.leaves(a._carry), jax.tree.leaves(b._carry)):
        np.testing.assert_array_equal(jax.device_get(x), jax.device_get(y))
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(ring_a.storage))[5 * E : 9 * E], np.asarray(jax.device_get(ring_b.storage))[: 4 * E])
    assert not plain.load_carry_state(saved)  # another family's carry: fresh episodes, no crash


def test_a_single_step_draws_the_noise_a_chunk_would_hand_it():
    """make_learner_step's step handed no noise draws its own smoothing noise
    for every step of every window, f32[B, L, act], from the stream a chunk
    pre-draws from: the same update either way."""
    from distributed_ddpg_tpu.learner import make_learner_step, noise_base_key, step_noise

    config = cfg()
    state = init_train_state(config, ObsSpec((OBS,), steps=L), ACT, 2)
    rng = np.random.default_rng(4)
    rows = jnp.asarray(rng.normal(size=(4, packed_width(ObsSpec((OBS,), steps=L), ACT))), jnp.float32)
    rows = rows.at[:, -L:].set(1.0).at[:, -2 * L : -L].set(0.0)  # full windows, no termination
    windows = unpack_windows(rows, OBS, ACT, L)
    step = jax.jit(make_learner_step(config, 1.0))
    noise = step_noise(config, noise_base_key(config), state.step, 4, ACT)
    assert noise.shape == (4, L, ACT) and float(jnp.max(jnp.abs(noise))) <= config.target_noise_clip
    alone, handed = step(state, windows), step(state, windows, noise)
    np.testing.assert_array_equal(alone.td_errors, handed.td_errors)
    assert alone.td_errors.shape == (4, L) and float(alone.metrics["seq_valid_frac"]) == 1.0


def test_occluded_stand_in_shows_half_of_the_parents_state():
    env, parent = make_jax_env(OCCLUDED_STAND_IN_ID), IsaacHumanoidStandIn()
    assert (env.obs_dim, env.state_dim, env.act_dim) == (54, 108, 21) and OCCLUDED_STAND_IN_ID in DEVICE_ONLY
    np.testing.assert_array_equal(env.a, parent.a)  # the same system as PQL's cell
    key = jax.random.PRNGKey(1)
    s, p = env.init(key), parent.init(key)
    np.testing.assert_array_equal(s.x, p.x)
    u = jnp.full((21,), 0.3)
    out, pout = env.step(s, u, key), parent.step(p, u, key)
    np.testing.assert_array_equal(out.obs, pout.obs[:54])
    np.testing.assert_array_equal(out.boot_obs, pout.boot_obs[:54])
    assert out.obs.shape == (54,) and out.state.x.shape == (108,) and float(out.reward) == float(pout.reward)
    # the hidden half moves what is seen a step later
    hidden = s._replace(x=s.x.at[54:].add(0.05))
    assert np.array_equal(env.observe(hidden), env.observe(s))
    assert not np.array_equal(env.step(hidden, u, key).obs, out.obs)
    spec = spec_of(make(OCCLUDED_STAND_IN_ID))
    assert (spec.obs_dim, spec.act_dim) == (54, 21)
    assert packed_width(ObsSpec.of_env(spec, 64), 21) == 5046  # ISSUE 53: "5,046 floats"


def test_rules_place_every_recurrent_leaf_and_a_window_is_no_transition():
    state = init_train_state(cfg(), ObsSpec((OBS,), steps=L), ACT, 0)
    for net in (state.actor_params, state.critic_params):
        assert recnet.is_recurrent(net)
        assert all(all(ax is None for ax in spec) for spec in jax.tree.leaves(
            net_pspec(net, 2), is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
    assert packed_width(ObsSpec((OBS,)), ACT) == 2 * OBS + ACT + 3
    assert packed_width(ObsSpec((OBS,), steps=L), ACT) == (L + 1) * OBS + L * (ACT + 3)
    with pytest.raises(ValueError, match="windows of seq_len"):
        from distributed_ddpg_tpu.parallel.learner import ShardedLearner

        ShardedLearner(cfg(), OBS, ACT, 1.0, mesh=one_device_mesh())  # transition rows for a recurrent learner


REFUSED = {
    "host workers": (dict(actor_backend="host", num_actors=2), "device actors only"),
    "a host pool beside the device pool": (dict(num_actors=1), "device actors only"),
    "served actors": (dict(serve_actors=True), "serv"),
    "the network front": (dict(front_port=8080), "stateless request"),
    "prioritised replay": (dict(prioritized=True), "refuses --prioritized"),
    "the kernel leg": (dict(fused_chunk="on"), "no loop over time"),
    "the fused beat": (dict(fused_beat="on"), "refuses --fused_beat"),
    "a superstep": (dict(superstep_beats=2), "superstep_beats"),
    "n-step rows": (dict(n_step=3), "refuses n_step > 1"),
    "sharded replay": (dict(replay_sharding="sharded"), "replay_sharding"),
    "host replay": (dict(host_replay=True), "host_replay"),
    "guardrails": (dict(guardrails=True), "refuses --guardrails"),
    # (sac and a categorical critic never reach _check_recurrent: the
    # families' own rule refuses them beside twin_critic first)
    "sac": (dict(sac=True), "own algorithm family"),
    "a categorical critic": (dict(distributional=True), "separate algorithm families"),
    "pixels": (dict(pixels=True, target_noise=0.0), "refuses --pixels"),
    "one critic": (dict(twin_critic=False), "twin_critic=True"),
    "a delayed policy": (dict(policy_delay=2), "policy_delay at 1"),
    "the action at the second layer": (dict(action_insert_layer=1), "action_insert_layer=0"),
    "OU exploration": (dict(exploration="ou"), "exploration=gaussian"),
    "the native backend": (dict(backend="native"), "jax_tpu"),
    "bfloat16 compute": (dict(compute_dtype="bfloat16"), "float32"),
    "a window of one step": (dict(seq_len=1), "seq_len must be >= 2"),
    "weight decay": (dict(weight_decay=1e-2), "weight_decay"),
    "copied targets": (dict(target_update_period=100), "target_update_period"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_check_recurrent_refuses_what_is_not_built(what):
    changed, says = REFUSED[what]
    with pytest.raises(ValueError, match=says):
        cfg(**changed)
    cfg()  # and the deployment's own flags stand
