"""Fused training megastep (parallel/megastep.py; docs/FUSED_BEAT.md):

- **bit-identity at the fused/unfused seam**: a fused beat sequence must
  equal the separate-dispatch sequence (learner chunk -> param swap ->
  rollout -> insert) BIT-FOR-BIT for fixed seeds — uniform + PER,
  replicated + sharded placement. This is the oracle that lets the fused
  path ship without its own quality story, exactly how the coalesced
  ingest and sharded placement anchored to their serial/replicated
  references.
- **guardrails inside the fused program**: the numeric:grad:nan@K chaos
  vector fires inside the beat, the health word reports it, and the
  update is dropped on device — guardrails=True keeps the fast path.
- **config validation**: the fused_beat rejection matrix.
- **train integration**: a fused (and guarded-fused) train_jax run
  completes its budget with fused_* observability in the records.
"""

import json

import numpy as np
import pytest

import jax

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.train import train_jax

OBS, ACT = 3, 1


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
        seed=3,
    )
    base.update(kw)
    return DDPGConfig(**base)


def _setup(config, sharded):
    """One (learner, pool, replay) stack with the ring pre-warmed by four
    standalone rollout chunks — both arms of the A/B build through here,
    so their pre-beat state is identical."""
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


@pytest.mark.parametrize("per", [False, True], ids=["uniform", "per"])
@pytest.mark.parametrize("sharded", [False, True],
                         ids=["replicated", "sharded"])
def test_fused_beat_bit_identical_to_separate_dispatches(per, sharded):
    """Three fused beats == three (chunk -> swap -> rollout -> insert)
    dispatch sequences: storage/ptr/size, the full TrainState, the
    sampling key, the rollout carry, and (PER) the priority vector are
    all bit-identical."""
    from distributed_ddpg_tpu.parallel.megastep import FusedMegastep

    config = _cfg(prioritized=per, fused_beat="on")
    lf, pf, rf = _setup(config, sharded)
    ms = FusedMegastep(config, lf, pf, rf)
    for _ in range(3):
        ms.run_beat(beta=0.5 if per else None)

    lu, pu, ru = _setup(config, sharded)
    for _ in range(3):
        if per:
            lu.run_sample_chunk_per(ru, 0.5)
        else:
            lu.run_sample_chunk(ru)
        pu.set_params(lu.state.actor_params)
        pu.run_chunk(ru)

    assert _leaves_equal(rf.storage, ru.storage)
    assert int(jax.device_get(rf.ptr)) == int(jax.device_get(ru.ptr))
    assert int(jax.device_get(rf.size)) == int(jax.device_get(ru.size))
    assert _leaves_equal(lf.state, lu.state)
    assert _leaves_equal(lf._key, lu._key)
    assert _leaves_equal(pf._carry, pu._carry)
    assert pf.steps_done == pu.steps_done
    if per:
        assert _leaves_equal(rf.priorities, ru.priorities)
        assert _leaves_equal(rf.max_priority, ru.max_priority)


TD3 = dict(twin_critic=True, target_noise=0.2, policy_delay=2)


def test_fused_beat_that_draws_noise_matches_separate_dispatches():
    """TD3's smoothing noise inside a beat: the base key of the stream is
    an argument of the beat (ShardedLearner._noise_key), handed on to the
    chunk body, and gives the bits the standalone chunk draws."""
    from distributed_ddpg_tpu.parallel.megastep import FusedMegastep

    config = _cfg(fused_beat="on", **TD3)
    lf, pf, rf = _setup(config, sharded=False)
    ms = FusedMegastep(config, lf, pf, rf)
    for _ in range(3):
        ms.run_beat()

    lu, pu, ru = _setup(config, sharded=False)
    for _ in range(3):
        lu.run_sample_chunk(ru)
        pu.set_params(lu.state.actor_params)
        pu.run_chunk(ru)

    assert _leaves_equal(rf.storage, ru.storage)
    assert _leaves_equal(lf.state, lu.state)
    assert _leaves_equal(lf._key, lu._key)


@pytest.mark.parametrize("program", ["beat", "superstep"])
def test_a_beat_that_draws_noise_lowers_to_one_text_at_every_seed(program):
    """The fused programs compose the chunk bodies, so they take the noise
    key as their last argument too: nothing derived from config.seed is in
    the text the persistent compile cache keys on."""
    from distributed_ddpg_tpu.parallel.megastep import FusedMegastep
    from distributed_ddpg_tpu.parallel.superstep import FusedSuperstep

    def lowered(seed):
        config = _cfg(fused_beat="on", seed=seed, **TD3)
        stack = _setup(config, sharded=False)
        if program == "beat":
            fused = FusedMegastep(config, *stack)
            fn = fused._beat
        else:
            fused = FusedSuperstep(config, *stack, beats=2)
            fn = fused._superstep
        args = fused.example_args()
        assert args[-1] is stack[0]._noise_key is not None
        return fn.lower(*args).as_text()

    assert lowered(1) == lowered(2)


def test_guarded_fused_beat_matches_guarded_dispatches():
    """The guarded composition is the same seam: guarded fused beats ==
    guarded separate dispatches, health word included."""
    from distributed_ddpg_tpu.parallel.megastep import FusedMegastep

    config = _cfg(fused_beat="on", guardrails=True)
    lf, pf, rf = _setup(config, sharded=False)
    ms = FusedMegastep(config, lf, pf, rf)
    for _ in range(3):
        ms.run_beat()

    lu, pu, ru = _setup(config, sharded=False)
    for _ in range(3):
        lu.run_sample_chunk(ru)
        pu.set_params(lu.state.actor_params)
        pu.run_chunk(ru)

    assert _leaves_equal(rf.storage, ru.storage)
    assert _leaves_equal(lf.state, lu.state)
    assert lf.poll_health() == lu.poll_health()


def test_guardrail_quarantine_fires_inside_fused_beat():
    """numeric:grad:nan@3 poisons the third guarded learner step INSIDE
    the fused program: the health word reports the skip, and the dropped
    update leaves params equal to the previous step's (the tree-select
    quarantine ran on device)."""
    from distributed_ddpg_tpu.parallel.megastep import FusedMegastep

    config = _cfg(
        fused_beat="on", guardrails=True, faults="numeric:grad:nan@3",
    )
    learner, pool, replay = _setup(config, sharded=False)
    ms = FusedMegastep(config, learner, pool, replay)
    ms.run_beat()  # steps 1-2: clean
    h = learner.poll_health()
    assert h["total"] == 2 and h["nonfinite"] == 0
    ms.run_beat()  # steps 3-4: step 3 poisoned
    h = learner.poll_health()
    assert h["total"] == 4
    assert h["nonfinite"] == 1
    assert h["skipped"] == 1
    # The probe kept every param leaf finite despite the NaN batch.
    for leaf in jax.tree.leaves(learner.state.actor_params):
        assert np.isfinite(np.asarray(jax.device_get(leaf))).all()


def test_fused_beat_rebuilds_after_learner_program_rebuild():
    """set_lr_scale (the rollback LR backoff) rebuilds the learner's
    chunk bodies; the next run_beat must recompose against them instead
    of dispatching the stale closures."""
    from distributed_ddpg_tpu.parallel.megastep import FusedMegastep

    config = _cfg(fused_beat="on")
    learner, pool, replay = _setup(config, sharded=False)
    ms = FusedMegastep(config, learner, pool, replay)
    ms.run_beat()
    v0 = ms._learner_version
    learner.set_lr_scale(0.5)
    ms.run_beat()
    assert ms._learner_version == learner.programs_version != v0


def test_fused_beat_config_validation():
    """The fused_beat rejection matrix (config.py; docs/FUSED_BEAT.md)."""
    with pytest.raises(ValueError, match="fused_beat must be"):
        _cfg(fused_beat="maybe")
    # Host actors have no compilable rollout leg.
    with pytest.raises(ValueError, match="actor_backend='device'"):
        DDPGConfig(fused_beat="on", actor_backend="host", num_actors=1)
    # The Pallas megakernel has no slot inside a larger program.
    with pytest.raises(ValueError, match="megakernel"):
        _cfg(fused_beat="on", fused_chunk="on")
    # The ratio gates need independently dispatchable phases.
    with pytest.raises(ValueError, match="ratio"):
        _cfg(fused_beat="on", max_ingest_ratio=1.0, max_learn_ratio=1.0)
    # serve_actors fails through the device-actor validation the fused
    # beat builds on; n_step > 1 no longer does (PR 40): the window folds
    # inside the rollout body the beat composes.
    assert _cfg(fused_beat="on", n_step=3).n_step == 3
    with pytest.raises(ValueError, match="serve"):
        _cfg(fused_beat="on", serve_actors=True)
    # The native backend has no device programs to fuse.
    with pytest.raises(ValueError, match="jax_tpu|native"):
        DDPGConfig(fused_beat="on", backend="native")
    # 'auto' and 'off' always parse.
    assert _cfg(fused_beat="auto").fused_beat == "auto"
    assert _cfg(fused_beat="off").fused_beat == "off"


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
        total_env_steps=400,
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


def test_train_fused_beat_with_guardrails(tmp_path):
    """End-to-end: guardrails=True no longer forces the unfused path —
    the fused megastep carries the guarded steady-state loop to its
    budget, with fused_* observability in the final record."""
    cfg = _train_cfg(tmp_path, guardrails=True)
    out = train_jax(cfg)
    assert out["fused_beat_active"] is True
    assert out["learner_steps"] > 0
    assert out["guardrail_skipped_updates"] == 0  # healthy run
    finals = [r for r in _records(cfg.log_path) if r["kind"] == "final"]
    assert finals
    for key in ("fused_beats", "fused_steps_per_s", "fused_rows_per_s",
                "fused_beat_ms", "fused_beat_p95"):
        assert key in finals[-1], f"{key} missing from the final record"
    assert out["devactor_env_steps"] > 0


def test_train_fused_vs_unfused_identical_end_state(tmp_path, one_chip):
    """TRAIN-LEVEL parity (the seam the unit parity above cannot see —
    loop accounting, cadences, warmup handoff): the same config run with
    fused_beat='on' and 'off' must finish with the same learner-step
    count, the same env-step production, and a bit-identical param
    checksum. Pins the whole dispatch-gating wiring — e.g. a fused beat
    that ALSO fell through to the unfused after_chunk would double the
    step accounting and extra-roll the envs, and only this test sees it.
    On one device (conftest.one_chip): the unfused loop keeps a rollout and
    a chunk program in flight, and on the 8 virtual devices XLA:CPU aborted
    this test about once in seven under six workers (PERF.md §7, 24). On
    the 8 devices, one beat's parity is the unit tests' above and the loop's
    accounting is the next test's; what nothing holds there is the unfused
    loop itself with collectives in its programs, and so the two modes' bit
    parity over a whole run on a mesh."""
    outs = {}
    for mode in ("on", "off"):
        cfg = _train_cfg(tmp_path, fused_beat=mode,
                         log_path=str(tmp_path / f"{mode}.jsonl"))
        outs[mode] = train_jax(cfg)
    assert outs["on"]["fused_beat_active"] is True
    assert outs["off"]["fused_beat_active"] is False
    assert outs["on"]["learner_steps"] == outs["off"]["learner_steps"]
    assert (
        outs["on"]["devactor_env_steps"] == outs["off"]["devactor_env_steps"]
    )
    assert outs["on"]["param_checksum"] == outs["off"]["param_checksum"]


def test_train_fused_on_the_mesh_counts_what_unfused_counts_on_one_chip(tmp_path, request):
    """The same seam on the 8 virtual devices, as far as it can be held
    without the unfused loop running there (the test above says why): the
    fused run on the mesh ends with the learner steps and the environment
    steps of the unfused run on one device. A beat that fell through to
    after_chunk as well, or a cadence that read the mesh's size, shows
    here; the parameters agree only as far as the order of a reduction
    over 8 devices lets them."""
    cfg = _train_cfg(tmp_path, fused_beat="on", log_path=str(tmp_path / "run_on.jsonl"))
    on = train_jax(cfg)
    assert on["fused_beat_active"] is True and on["mesh_data_axis"] == 8
    request.getfixturevalue("one_chip")  # from here on, train() takes one device
    cfg = _train_cfg(tmp_path, fused_beat="off", log_path=str(tmp_path / "run_off.jsonl"))
    off = train_jax(cfg)
    assert off["fused_beat_active"] is False and off["mesh_data_axis"] == 1
    for key in ("learner_steps", "env_steps", "devactor_env_steps"):
        assert on[key] == off[key] > 0, key
    assert on["param_checksum"] == pytest.approx(off["param_checksum"], rel=1e-2)


def test_train_fused_beat_off_keeps_dispatch_per_phase(tmp_path, one_chip):
    """fused_beat='off' pins the dispatch-per-phase loop; the summary
    reports the gating fact and no fused_* fields ride the records. On one
    device, as the parity test above and for its reason."""
    cfg = _train_cfg(tmp_path, fused_beat="off")
    out = train_jax(cfg)
    assert out["fused_beat_active"] is False
    assert out["learner_steps"] > 0
    finals = [r for r in _records(cfg.log_path) if r["kind"] == "final"]
    assert "fused_beats" not in finals[-1]
