"""Numerical-health guardrails (guardrails.py; docs/RESILIENCE.md
'Numerical health'): probe units (NaN/Inf/z-score triggers, injection
ordinals, bad-row capture), the zero-overhead disabled path and its
bit-identity to the pre-guardrail programs, rollback support machinery
(diverged-checkpoint quarantine, direct source quarantine), and the
tier-1 chaos acceptance run — `numeric:grad:nan@k` must roll the run back
to a manifest-valid step < k and still complete its budget with finite
params."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ddpg_tpu import checkpoint as ckpt_lib
from distributed_ddpg_tpu import guardrails
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.faults import FaultPlan
from distributed_ddpg_tpu.learner import (
    init_train_state,
    make_learner_step,
)
from distributed_ddpg_tpu.types import Batch

OBS, ACT, B = 3, 1, 16


def _cfg(**kw):
    return DDPGConfig(
        actor_hidden=(8, 8), critic_hidden=(8, 8), batch_size=B, **kw
    )


def _batch(rng, reward_scale=1.0, poison_obs=False, poison_reward=None):
    obs = rng.standard_normal((B, OBS)).astype(np.float32)
    if poison_obs:
        obs[0, 0] = np.nan
    reward = (reward_scale * rng.standard_normal(B)).astype(np.float32)
    if poison_reward is not None:
        reward[0] = poison_reward
    return Batch(
        obs=jnp.asarray(obs),
        action=jnp.asarray(rng.standard_normal((B, ACT)).astype(np.float32)),
        reward=jnp.asarray(reward),
        discount=jnp.full((B,), 0.99, jnp.float32),
        next_obs=jnp.asarray(
            rng.standard_normal((B, OBS)).astype(np.float32)
        ),
        weight=jnp.ones((B,), jnp.float32),
    )


def _leaves_equal(a, b):
    return all(
        np.array_equal(x, y)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# ---------------------------------------------------------------------------
# faults grammar
# ---------------------------------------------------------------------------


def test_numeric_fault_grammar_parses_and_routes():
    plan = FaultPlan.parse(
        "numeric:grad:nan@500;numeric:loss:spike@7;numeric:replay:inf@42"
    )
    assert plan.numeric_steps() == {"grad": (500,), "loss": (7,)}
    assert plan.numeric_replay_rows() == (42,)
    # Config-level validation accepts the same specs.
    _cfg(faults="numeric:grad:nan@500", guardrails=True, data_axis=1)


@pytest.mark.parametrize(
    "spec",
    [
        "numeric:grad:inf@5",      # wrong kind for the target
        "numeric:loss:nan@5",
        "numeric:params:nan@5",    # unknown target
        "numeric:grad:crash@5",    # non-numeric kind
    ],
)
def test_numeric_fault_grammar_rejects_bad_pairs(spec):
    with pytest.raises(ValueError, match="numeric"):
        FaultPlan.parse(spec)


# ---------------------------------------------------------------------------
# probe units (unjitted guarded step)
# ---------------------------------------------------------------------------


def test_guarded_step_passes_healthy_and_skips_nan_batch():
    cfg = _cfg()
    step = make_learner_step(cfg, 1.0)
    guarded = guardrails.make_guarded_step(step, zmax=8.0, warmup=64)
    state = init_train_state(cfg, OBS, ACT, seed=0)
    g = guardrails.init_guard_state()
    rng = np.random.default_rng(0)

    healthy, g, td, m = guarded(
        state, g, _batch(rng), jnp.asarray(False)
    )
    assert int(g.total) == 1 and int(g.skipped) == 0
    assert not _leaves_equal(healthy.actor_params, state.actor_params)
    assert np.all(np.isfinite(np.asarray(td)))

    # A NaN-poisoned batch: update dropped (params/opt identical), step
    # counter still advances, TD zeroed, metrics zeroed.
    bad_state, g, td, m = guarded(
        healthy, g, _batch(rng, poison_obs=True), jnp.asarray(False)
    )
    assert int(g.total) == 2
    assert int(g.nonfinite) == 1 and int(g.skipped) == 1
    assert _leaves_equal(bad_state.actor_params, healthy.actor_params)
    assert _leaves_equal(bad_state.critic_opt, healthy.critic_opt)
    assert int(bad_state.step) == int(healthy.step) + 1
    assert np.all(np.asarray(td) == 0.0)
    assert float(m["critic_loss"]) == 0.0

    # An Inf reward (the poisoned-replay-row shape) trips the same path.
    _, g, _, _ = guarded(
        bad_state, g, _batch(rng, poison_reward=np.inf), jnp.asarray(False)
    )
    assert int(g.nonfinite) == 2


def test_guarded_step_zscore_spike_detector():
    cfg = _cfg()
    step = make_learner_step(cfg, 1.0)
    guarded = guardrails.make_guarded_step(step, zmax=6.0, warmup=8)
    state = init_train_state(cfg, OBS, ACT, seed=0)
    g = guardrails.init_guard_state()
    rng = np.random.default_rng(1)
    for _ in range(12):  # past warmup: EWMA armed
        state, g, _, _ = guarded(state, g, _batch(rng), jnp.asarray(False))
    assert int(g.warm) >= 8 and int(g.skipped) == 0

    spiked, g, _, _ = guarded(
        state, g, _batch(rng, reward_scale=1e6), jnp.asarray(False)
    )
    assert int(g.spikes) == 1 and int(g.skipped) == 1
    assert _leaves_equal(spiked.actor_params, state.actor_params)
    # The spike must NOT have polluted its own baseline: the next healthy
    # step passes.
    _, g, _, _ = guarded(spiked, g, _batch(rng), jnp.asarray(False))
    assert int(g.skipped) == 1


def test_guarded_step_pre_bad_flag_forces_skip():
    cfg = _cfg()
    step = make_learner_step(cfg, 1.0)
    guarded = guardrails.make_guarded_step(step, zmax=8.0, warmup=64)
    state = init_train_state(cfg, OBS, ACT, seed=0)
    g = guardrails.init_guard_state()
    rng = np.random.default_rng(2)
    new, g, _, _ = guarded(state, g, _batch(rng), jnp.asarray(True))
    assert int(g.skipped) == 1
    assert _leaves_equal(new.actor_params, state.actor_params)


def test_numeric_injection_fires_once_per_monotonic_ordinal():
    cfg = _cfg()
    step = make_learner_step(cfg, 1.0)
    guarded = guardrails.make_guarded_step(
        step, zmax=8.0, warmup=64, inject={"grad": (3,)}
    )
    state = init_train_state(cfg, OBS, ACT, seed=0)
    g = guardrails.init_guard_state()
    rng = np.random.default_rng(3)
    skipped_at = []
    for i in range(5):
        prev = int(g.skipped)
        state, g, _, _ = guarded(state, g, _batch(rng), jnp.asarray(False))
        if int(g.skipped) > prev:
            skipped_at.append(i + 1)
    assert skipped_at == [3]
    # Ordinals key on GuardState.total — re-running the same step numbers
    # with a PRESERVED clock (the rollback contract) must not re-fire.
    g2 = guardrails.init_guard_state(total=int(g.total))
    for _ in range(3):
        state, g2, _, _ = guarded(state, g2, _batch(rng), jnp.asarray(False))
    assert int(g2.skipped) == 0


def test_batch_row_health_screens_and_captures_indices():
    rng = np.random.default_rng(4)
    packed = rng.standard_normal((4, 8, 5)).astype(np.float32)
    packed[1, 2, 0] = np.inf
    packed[3, 0, 4] = np.nan
    idx = rng.integers(0, 1000, (4, 8)).astype(np.int32)
    pre_bad, count, bad_idx = guardrails.batch_row_health(
        jnp.asarray(packed), jnp.asarray(idx)
    )
    assert list(np.asarray(pre_bad)) == [False, True, False, True]
    assert int(count) == 2
    got = set(int(v) for v in np.asarray(bad_idx) if v >= 0)
    assert got == {int(idx[1, 2]), int(idx[3, 0])}
    # Host-fed path: indices unknown -> all -1, counts still real.
    _, count2, none_idx = guardrails.batch_row_health(
        jnp.asarray(packed), None
    )
    assert int(count2) == 2 and np.all(np.asarray(none_idx) == -1)


# ---------------------------------------------------------------------------
# learner integration: disabled path, parity, health plumbing
# ---------------------------------------------------------------------------


def _filled_learner(guard, rng_seed=0, faults="", per=False, **cfg_kw):
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.replay.device import (
        DevicePrioritizedReplay,
        DeviceReplay,
    )

    cfg = _cfg(
        guardrails=guard, faults=faults, prioritized=per, **cfg_kw,
    )
    # One-device mesh: the conftest's 8 virtual CPU devices would shard
    # the batch; single-device keeps the frozen-reference parity simple.
    mesh = mesh_lib.make_mesh(1, 1, devices=jax.devices()[:1])
    learner = ShardedLearner(cfg, OBS, ACT, 1.0, chunk_size=4, mesh=mesh)
    cls = DevicePrioritizedReplay if per else DeviceReplay
    rep = cls(
        1000, OBS, ACT, mesh=learner.mesh, block_size=64,
        track_sources=guard,
    )
    rng = np.random.default_rng(rng_seed)
    rep.add_packed(
        rng.standard_normal((256, rep.width)).astype(np.float32), source=1
    )
    rep.drain_pending()
    return learner, rep


def test_disabled_path_has_no_probe_surface():
    learner, rep = _filled_learner(guard=False)
    assert not learner.guard_enabled
    assert learner.poll_health() is None
    assert len(learner.bad_indices()) == 0
    assert not hasattr(learner, "_guard")
    out = learner.run_sample_chunk(rep)
    assert np.isfinite(float(out.metrics["critic_loss"]))
    assert learner.poll_health() is None  # still nothing to report


def test_guardrails_off_bit_identical_to_pre_guardrail_programs():
    """The acceptance parity pin: with guardrails disabled, the sample-
    chunk program must produce BIT-identical state to the pre-guardrail
    implementation (frozen here as a reference: draw_chunk + lax.scan
    over make_learner_step, the exact PR-6-era path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_ddpg_tpu.learner import StepOutput
    from distributed_ddpg_tpu.types import unpack_batch

    learner, rep = _filled_learner(guard=False)
    cfg = learner.config
    step = make_learner_step(cfg, 1.0, action_offset=0.0)
    K, BB = 4, learner.global_batch

    def ref_fn(s, key, storage, size):
        key, sub = jax.random.split(key)
        idx = jax.random.randint(sub, (K, BB), 0, jnp.maximum(size, 1))
        packed = storage[idx]
        packed = jax.lax.with_sharding_constraint(
            packed, NamedSharding(learner.mesh, P(None, "data", None))
        )
        batches = unpack_batch(packed, OBS, ACT)

        def body(carry, b):
            out = step(carry, b)
            return out.state, (out.td_errors, out.metrics)

        s, (tds, ms) = jax.lax.scan(body, s, batches, unroll=4)
        return StepOutput(
            state=s, td_errors=tds, metrics=jax.tree.map(jnp.mean, ms)
        ), key

    ref = jax.jit(ref_fn)
    rs = jax.tree.map(jnp.asarray, jax.device_get(learner.state))
    rk = jax.random.PRNGKey(cfg.seed)
    storage, size = rep.device_state()
    for _ in range(4):
        learner.run_sample_chunk(rep)
        out, rk = ref(rs, rk, storage, size)
        rs = out.state
    assert _leaves_equal(
        jax.device_get(learner.state), jax.device_get(rs)
    ), "guardrails-off diverged from the pre-guardrail reference"


@pytest.mark.parametrize(
    "per",
    [False, pytest.param(True, marks=pytest.mark.slow)],  # PER build is
    # a second full compile; the uniform variant carries tier-1
)
def test_guardrails_on_healthy_matches_off(per):
    """Armed-but-clean guardrails must be behavior-neutral: same draws,
    same math, zero skips — states match to float tolerance (the extra
    probe consumers change XLA fusion, so bitwise is not guaranteed ON;
    bit-identity is the OFF path's contract, pinned above)."""
    outs = []
    for guard in (False, True):
        learner, rep = _filled_learner(guard=guard, per=per)
        for _ in range(4):
            if per:
                learner.run_sample_chunk_per(rep, 0.5)
            else:
                learner.run_sample_chunk(rep)
        outs.append(jax.device_get(learner.state))
        if guard:
            h = learner.poll_health()
            assert h["total"] == 16 and h["skipped"] == 0
            assert h["bad_rows"] == 0
    for x, y in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        np.testing.assert_allclose(
            np.asarray(x, np.float64), np.asarray(y, np.float64),
            rtol=2e-4, atol=2e-5,
        )


def test_bad_rows_attribution_reset_clock_and_reseed():
    """One learner session covering the rollback-support plumbing: bad
    sampled rows are detected and attributed to their ingest source;
    reset_guard keeps the cumulative counters and monotonic clock while
    clearing the reportable health word; reseed changes the sampling
    key. (set_lr_scale's recompile is exercised end-to-end by the chaos
    rollback test below — no separate compile paid here.)"""
    learner, rep = _filled_learner(guard=True)
    rng = np.random.default_rng(9)
    bad = rng.standard_normal((64, rep.width)).astype(np.float32)
    bad[:, OBS + ACT] = np.inf  # reward column
    rep.add_packed(bad, source=3)
    rep.drain_pending()
    for _ in range(4):
        learner.run_sample_chunk(rep)
    h = learner.poll_health()
    assert h["bad_rows"] > 0 and h["skipped"] > 0
    idx = learner.bad_indices()
    assert len(idx) > 0
    srcs = set(int(s) for s in rep.sources_of(idx))
    assert srcs == {3}, f"bad rows misattributed: {srcs}"

    learner.reset_guard()
    assert learner.poll_health() is None
    learner.run_sample_chunk(rep)
    after = learner.poll_health()
    # Cumulative counters and the monotonic clock survived the reset
    # (the EWMA fields reset; chunk 5 of 4 steps -> total 20).
    assert after["skipped"] >= h["skipped"] and after["total"] == 20

    k0 = np.asarray(jax.device_get(learner._key)).copy()
    learner.reseed(7)
    assert not np.array_equal(
        k0, np.asarray(jax.device_get(learner._key))
    )


# ---------------------------------------------------------------------------
# rollback support machinery
# ---------------------------------------------------------------------------


def test_discard_above_quarantines_diverged_checkpoints(tmp_path):
    cfg = _cfg()
    state = init_train_state(cfg, 4, 2, seed=0)
    for step in (10, 20, 30):
        ckpt_lib.save(str(tmp_path), step, state, None, cfg, keep=0)
    discarded = ckpt_lib.discard_above(str(tmp_path), 10)
    assert discarded == [20, 30]
    assert ckpt_lib.latest_step(str(tmp_path)) == 10
    assert ckpt_lib.valid_steps(str(tmp_path)) == [10]
    for s in (20, 30):
        assert (tmp_path / f"diverged_step_{s}").is_dir()
        assert not (tmp_path / f"manifest_{s}.json").exists()
    assert ckpt_lib.discard_above(str(tmp_path), 10) == []


def test_pool_quarantine_source_direct():
    from distributed_ddpg_tpu.actors.pool import ActorPool
    from distributed_ddpg_tpu.envs.registry import EnvSpec

    spec = EnvSpec(
        obs_dim=OBS, act_dim=ACT,
        action_low=np.full(ACT, -1.0, np.float32),
        action_high=np.full(ACT, 1.0, np.float32),
    )
    pool = ActorPool(_cfg(num_actors=2), spec)
    assert pool.quarantine_source(0, why="numeric")
    assert pool.quarantined_count == 1
    assert not pool.quarantine_source(0), "double-quarantine must no-op"
    assert not pool.quarantine_source(99), "bad slot id must no-op"
    assert pool.recovery_counters()["actor_quarantined"] == 1


def test_config_validation():
    with pytest.raises(ValueError, match="scan path"):
        _cfg(guardrails=True, fused_chunk="on")
    with pytest.raises(ValueError, match="jax_tpu"):
        _cfg(guardrails=True, backend="native")
    with pytest.raises(ValueError, match="guardrail_lr_backoff"):
        _cfg(guardrail_lr_backoff=0.0)
    with pytest.raises(ValueError, match="guardrail_zmax"):
        _cfg(guardrail_zmax=-1.0)


# ---------------------------------------------------------------------------
# tools rendering
# ---------------------------------------------------------------------------


def test_tools_runs_guardrail_digest(tmp_path):
    from distributed_ddpg_tpu.tools.runs import render_summary, summarize_run

    path = tmp_path / "run.jsonl"
    recs = [
        {"kind": "train", "step": 100, "wall_time": 1.0,
         "guardrail_rollbacks": 0, "guardrail_skipped_updates": 0},
        {"kind": "final", "step": 200, "wall_time": 2.0,
         "guardrail_rollbacks": 1, "guardrail_skipped_updates": 3,
         "guardrail_last_rollback_step": 120},
    ]
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    digest = summarize_run(str(path))
    assert digest["guardrail"]["guardrail_rollbacks"]["last"] == 1
    assert "numerical health" in render_summary(digest)


# ---------------------------------------------------------------------------
# tier-1 chaos acceptance: injected NaN -> rollback -> budget completes
# ---------------------------------------------------------------------------


def _records(path):
    out = []
    with open(path) as f:
        for line in f:
            if line.strip().startswith("{"):
                out.append(json.loads(line))
    return out


# Re-tiered to slow (ISSUE 15 tier-1 budget): 81s rollback chaos train run; the healthy-parity + unit battery keep
# guardrails tier-1 coverage
@pytest.mark.slow
def test_numeric_nan_chaos_rolls_back_and_completes(tmp_path):
    """The acceptance run (ISSUE 7): a CPU training run with an injected
    `numeric:grad:nan@k` must complete its env budget, report >= 1
    guardrail rollback whose restore step is manifest-valid and < k, and
    end with finite params."""
    from distributed_ddpg_tpu.train import train_jax

    K = 400
    cfg = DDPGConfig(
        env_id="Pendulum-v1",
        actor_hidden=(16, 16), critic_hidden=(16, 16),
        num_actors=1,
        total_env_steps=2_000,
        replay_min_size=256,
        replay_capacity=20_000,
        eval_every=0,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_every=100,
        log_path=str(tmp_path / "g.jsonl"),
        max_learn_ratio=1.0,
        max_ingest_ratio=1.0,
        actor_throttle_s=0.002,
        guardrails=True,
        guardrail_rollback_k=1,   # one NaN step is enough to repair
        guardrail_lr_cooldown_steps=500,
        faults=f"numeric:grad:nan@{K}",
    )
    out = train_jax(cfg)

    assert out["learner_steps"] > K, f"budget did not complete: {out}"
    assert not out["numeric_failed"]
    assert out["guardrail_rollbacks"] >= 1
    assert out["guardrail_nonfinite_steps"] >= 1
    restored = out["guardrail_last_rollback_step"]
    assert 0 < restored < K, (
        f"rollback must restore a pre-divergence step < {K}: {restored}"
    )
    # End params are finite (the poisoned update never landed).
    assert np.isfinite(out["param_checksum"])
    # The final JSONL record carries the guardrail digest.
    final = [r for r in _records(cfg.log_path) if r["kind"] == "final"][-1]
    assert final["guardrail_rollbacks"] == out["guardrail_rollbacks"]
    assert final["guardrail_last_rollback_step"] == restored
    # The latest retained checkpoint is from the REPAIRED timeline and
    # verifies clean.
    step = ckpt_lib.latest_step(cfg.checkpoint_dir)
    assert step is not None
    ok, why = ckpt_lib.verify_checkpoint(cfg.checkpoint_dir, step)
    assert ok, why


@pytest.mark.slow
def test_numeric_abort_exhausted_budget_flags_exit_contract(tmp_path):
    """Rollback budget 0: the first sustained-divergence trigger must
    take the documented numeric abort — run ends early, numeric_failed
    rides the summary (main() exits 77), no final eval of poisoned
    params."""
    from distributed_ddpg_tpu.train import train_jax

    cfg = DDPGConfig(
        env_id="Pendulum-v1",
        actor_hidden=(16, 16), critic_hidden=(16, 16),
        num_actors=1,
        total_env_steps=100_000,   # far beyond: the abort must end it
        replay_min_size=256,
        replay_capacity=20_000,
        eval_every=0,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_every=100,
        log_path=str(tmp_path / "a.jsonl"),
        max_learn_ratio=1.0,
        max_ingest_ratio=1.0,
        actor_throttle_s=0.002,
        guardrails=True,
        guardrail_rollback_k=1,
        guardrail_max_rollbacks=0,
        faults="numeric:grad:nan@50",
    )
    out = train_jax(cfg)
    assert out["numeric_failed"]
    assert out["guardrail_rollbacks"] == 0
    assert out["final_return"] is None
    assert out["learner_steps"] < 5_000


# ---------------------------------------------------------------------------
# slow: poisoned replay row -> source quarantine, end to end
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_numeric_replay_poison_quarantines_source(tmp_path):
    """`numeric:replay:inf@k` poisons a real ingested row; sampling it
    must skip the step, record the row, attribute it to the worker that
    produced it, and quarantine that slot through the pool breaker."""
    from distributed_ddpg_tpu.train import train_jax

    cfg = DDPGConfig(
        env_id="Pendulum-v1",
        actor_hidden=(16, 16), critic_hidden=(16, 16),
        num_actors=2,
        total_env_steps=2_500,
        replay_min_size=256,
        replay_capacity=20_000,
        eval_every=0,
        log_path=str(tmp_path / "q.jsonl"),
        max_learn_ratio=1.0,
        max_ingest_ratio=1.0,
        actor_throttle_s=0.002,
        guardrails=True,
        guardrail_rollback_k=0,        # isolate the quarantine path
        guardrail_source_offenses=1,
        faults="numeric:replay:inf@300",
    )
    out = train_jax(cfg)
    assert out["guardrail_bad_rows"] >= 1
    assert out["guardrail_source_quarantines"] >= 1
    assert out["learner_steps"] > 0
