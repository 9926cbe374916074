"""Compile-once multi-beat superstep (parallel/superstep.py;
docs/FUSED_BEAT.md §superstep):

- **bit-identity at the superstep/beat seam**: a B-beat superstep (one
  `lax.fori_loop` dispatch) must equal B sequential fused beats
  BIT-FOR-BIT for fixed seeds — uniform + PER, replicated + sharded,
  guarded + unguarded. This is the oracle that lets the superstep ship
  without its own quality story, the same anchoring discipline the fused
  beat itself used against the dispatch-per-phase loop. The load-bearing
  structural fact (recorded in the module docstring): ALL B beats run
  inside the loop body, which XLA compiles as its own isolated
  computation — a beat inlined into the main computation gets
  cross-optimized with its surroundings and drifts at the ULP level.
- **one host sync per superstep**: stats/health accumulate in the
  device-side carry; the dispatch counter proves B beats rode one
  dispatch.
- **quarantine mid-superstep**: the chaos vector fires INSIDE the loop,
  the stacked health carry reports WHICH beat went bad
  (first_bad_beat), and the drop semantics match the per-beat path.
- **config validation** and **train integration**.
"""

import json

import numpy as np
import pytest

import jax

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.train import train_jax


def _cfg(**kw):
    base = dict(
        env_id="Pendulum-v1",
        actor_backend="device",
        num_actors=0,
        device_actor_envs=8,
        device_actor_chunk=2,
        learner_chunk=2,
        batch_size=8,
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        replay_capacity=256,
        fused_chunk="off",
        fused_beat="on",
        seed=3,
    )
    base.update(kw)
    return DDPGConfig(**base)


def _setup(config, sharded):
    """One (learner, pool, replay) stack with the ring pre-warmed by four
    standalone rollout chunks — both arms of the A/B build through here,
    so their pre-dispatch state is identical (test_megastep.py idiom)."""
    from distributed_ddpg_tpu.actors.device_pool import DeviceActorPool
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.replay.device import (
        DevicePrioritizedReplay,
        DeviceReplay,
    )

    n = 2 if sharded else 1
    placement = "sharded" if sharded else "replicated"
    mesh = mesh_lib.make_mesh(n, 1, devices=jax.devices("cpu")[:n])
    pool = DeviceActorPool(config, mesh=mesh)
    learner = ShardedLearner(
        config, pool.obs_dim, pool.act_dim, pool.action_scale,
        action_offset=pool.action_offset, mesh=mesh, chunk_size=2,
        replay_sharding=placement,
    )
    cls = DevicePrioritizedReplay if config.prioritized else DeviceReplay
    replay = cls(
        config.replay_capacity, pool.obs_dim, pool.act_dim, mesh=mesh,
        block_size=16, async_ship=False, replay_sharding=placement,
    )
    pool.set_params(learner.state.actor_params)
    for _ in range(4):
        pool.run_chunk(replay)
    return learner, pool, replay


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(
        np.array_equal(
            np.asarray(jax.device_get(x)), np.asarray(jax.device_get(y))
        )
        for x, y in zip(la, lb)
    )


def _assert_stacks_equal(sup, seq, per):
    ls, rs = sup[2], seq[2]
    assert _leaves_equal(ls.storage, rs.storage)
    assert int(jax.device_get(ls.ptr)) == int(jax.device_get(rs.ptr))
    assert int(jax.device_get(ls.size)) == int(jax.device_get(rs.size))
    assert _leaves_equal(sup[0].state, seq[0].state)
    assert _leaves_equal(sup[0]._key, seq[0]._key)
    assert _leaves_equal(sup[1]._carry, seq[1]._carry)
    if per:
        assert _leaves_equal(ls.priorities, rs.priorities)
        assert _leaves_equal(ls.max_priority, rs.max_priority)


@pytest.mark.parametrize("guard", [False, True],
                         ids=["unguarded", "guarded"])
@pytest.mark.parametrize("per", [False, True], ids=["uniform", "per"])
@pytest.mark.parametrize("sharded", [False, True],
                         ids=["replicated", "sharded"])
def test_superstep_bit_identical_to_sequential_beats(per, sharded, guard):
    """One B=4 superstep == four sequential fused beats: storage/ptr/
    size, the full TrainState, the sampling key, the rollout carry,
    (PER) priorities, and (guarded) the health view are bit-identical."""
    from distributed_ddpg_tpu.parallel.megastep import FusedMegastep
    from distributed_ddpg_tpu.parallel.superstep import FusedSuperstep

    config = _cfg(prioritized=per, guardrails=guard, superstep_beats=4)
    sup = _setup(config, sharded)
    ss = FusedSuperstep(config, *sup)
    ss.run_superstep(betas=0.5 if per else None)

    seq = _setup(config, sharded)
    ms = FusedMegastep(config, *seq)
    for _ in range(4):
        ms.run_beat(beta=0.5 if per else None)

    _assert_stacks_equal(sup, seq, per)
    if guard:
        hs = sup[0].poll_health()
        # The stacked health carry adds the per-beat attribution key;
        # the cumulative counters themselves must match the scalar path.
        assert hs.pop("first_bad_beat") == -1
        assert hs == seq[0].poll_health()


def test_superstep_b1_matches_single_beats():
    """B=1 is today's behavior: three one-beat supersteps == three
    per-beat dispatches, bit-for-bit (the degenerate-loop oracle)."""
    from distributed_ddpg_tpu.parallel.megastep import FusedMegastep
    from distributed_ddpg_tpu.parallel.superstep import FusedSuperstep

    config = _cfg(superstep_beats=1)
    sup = _setup(config, sharded=False)
    ss = FusedSuperstep(config, *sup)
    for _ in range(3):
        ss.run_superstep()

    seq = _setup(config, sharded=False)
    ms = FusedMegastep(config, *seq)
    for _ in range(3):
        ms.run_beat()

    _assert_stacks_equal(sup, seq, per=False)


def test_superstep_single_host_sync_per_dispatch():
    """B beats ride ONE dispatch: the stats layer counts supersteps and
    beats separately, and fused_beat_ms reads as whole-dispatch wall
    amortized over B (the /B headline)."""
    from distributed_ddpg_tpu.parallel.superstep import FusedSuperstep

    config = _cfg(superstep_beats=4)
    learner, pool, replay = _setup(config, sharded=False)
    ss = FusedSuperstep(config, learner, pool, replay)
    for _ in range(2):
        ss.run_superstep()
    snap = ss.snapshot()
    assert snap["fused_supersteps"] == 2
    assert snap["fused_beats"] == 8
    assert snap["fused_superstep_beats"] == 4.0
    assert snap["fused_beat_ms"] > 0


def test_quarantine_mid_superstep_reports_first_bad_beat():
    """numeric:grad:nan@3 poisons learner step 3 — beat index 1 of the
    first B=2 superstep. The stacked health carry localizes it
    (first_bad_beat=1), the update is dropped on device, and the next
    (clean) superstep reports first_bad_beat=-1 with cumulative
    counters intact."""
    from distributed_ddpg_tpu.parallel.superstep import FusedSuperstep

    config = _cfg(
        guardrails=True, faults="numeric:grad:nan@3", superstep_beats=2,
    )
    learner, pool, replay = _setup(config, sharded=False)
    ss = FusedSuperstep(config, learner, pool, replay)
    ss.run_superstep()  # steps 1-4: step 3 poisoned, in beat index 1
    h = learner.poll_health()
    assert h["total"] == 4
    assert h["nonfinite"] == 1
    assert h["skipped"] == 1
    assert h["first_bad_beat"] == 1
    for leaf in jax.tree.leaves(learner.state.actor_params):
        assert np.isfinite(np.asarray(jax.device_get(leaf))).all()
    ss.run_superstep()  # steps 5-8: clean
    h = learner.poll_health()
    assert h["total"] == 8
    assert h["nonfinite"] == 1
    assert h["first_bad_beat"] == -1


def test_superstep_rebuilds_after_learner_program_rebuild():
    """set_lr_scale (the rollback LR backoff) rebuilds the learner's
    chunk bodies; the next run_superstep must recompose the loop body
    against them instead of dispatching the stale closures."""
    from distributed_ddpg_tpu.parallel.superstep import FusedSuperstep

    config = _cfg(superstep_beats=2)
    learner, pool, replay = _setup(config, sharded=False)
    ss = FusedSuperstep(config, learner, pool, replay)
    ss.run_superstep()
    v0 = ss._learner_version
    learner.set_lr_scale(0.5)
    ss.run_superstep()
    assert ss._learner_version == learner.programs_version != v0


def test_superstep_config_validation():
    """The superstep_beats rejection matrix (config.py)."""
    with pytest.raises(ValueError, match="superstep_beats must be"):
        _cfg(superstep_beats=0)
    # B > 1 composes FUSED beats; there is no unfused dispatch to wrap.
    with pytest.raises(ValueError, match="superstep_beats > 1"):
        _cfg(fused_beat="off", superstep_beats=2)
    assert _cfg(fused_beat="off", superstep_beats=1).superstep_beats == 1
    assert _cfg(superstep_beats=4).superstep_beats == 4


def _train_cfg(tmp_path, **kw):
    base = dict(
        env_id="Pendulum-v1",
        actor_backend="device",
        num_actors=0,
        device_actor_envs=8,
        device_actor_chunk=2,
        learner_chunk=2,
        batch_size=16,
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        replay_capacity=2048,
        replay_min_size=64,
        # 64 warmup rows + 384 steady rows = 24 beats = 6 B=4 supersteps:
        # both arms land exactly on the budget, so the parity assert
        # compares equal-work runs (budget checks run once per superstep).
        total_env_steps=448,
        eval_every=0,
        eval_episodes=1,
        fused_chunk="off",
        fused_beat="on",
        log_path=str(tmp_path / "run.jsonl"),
    )
    base.update(kw)
    return DDPGConfig(**base)


def _records(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_train_superstep_matches_per_beat_dispatch(tmp_path):
    """TRAIN-LEVEL parity (the seam the unit oracle cannot see — loop
    accounting, cadences, warmup handoff): superstep_beats=4 and =1
    finish with the same learner-step count, env-step production, and a
    bit-identical param checksum, and the superstep run reports the
    dispatch amortization in its final record."""
    outs = {}
    for beats in (1, 4):
        cfg = _train_cfg(tmp_path, superstep_beats=beats,
                         log_path=str(tmp_path / f"b{beats}.jsonl"))
        outs[beats] = train_jax(cfg)
    assert outs[4]["fused_beat_active"] is True
    assert outs[4]["learner_steps"] == outs[1]["learner_steps"]
    assert outs[4]["devactor_env_steps"] == outs[1]["devactor_env_steps"]
    assert outs[4]["param_checksum"] == outs[1]["param_checksum"]
    finals = [r for r in _records(str(tmp_path / "b4.jsonl"))
              if r["kind"] == "final"]
    assert finals
    final = finals[-1]
    for key in ("fused_beats", "fused_supersteps", "fused_superstep_beats",
                "fused_beat_ms"):
        assert key in final, f"{key} missing from the final record"
    assert final["fused_superstep_beats"] == 4.0
    assert final["fused_beats"] == 4 * final["fused_supersteps"]


def test_train_superstep_guarded_smoke(tmp_path):
    """Guarded superstep end-to-end: the stacked health carry feeds the
    monitor without tripping quarantine on a healthy run."""
    cfg = _train_cfg(tmp_path, superstep_beats=4, guardrails=True)
    out = train_jax(cfg)
    assert out["fused_beat_active"] is True
    assert out["learner_steps"] > 0
    assert out["guardrail_skipped_updates"] == 0
