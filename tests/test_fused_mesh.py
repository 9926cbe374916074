"""Megakernel x mesh composition (parallel/learner.py fused-mesh path).

Three layers of evidence, mirroring how the path is built:

1. EXACT parity: the fused-mesh chunk must equal a host-built reference of
   the algorithm it claims to implement — per-device megakernel chunks on
   reproduced per-device draws, float state averaged at the boundary
   (K-step local SGD). Interpret mode = bit-level oracle, so tolerances
   are tight.
2. BOUNDED divergence: local SGD vs the scan path's per-step psum on the
   same buffer must land within a small fraction of the total parameter
   movement — the tolerance-bounded scan parity VERDICT r3 #4 asks for.
3. Activation envelope: data-only meshes compose; model-parallel meshes
   and fused_mesh='off' fall back to scan without error; fused_chunk='on'
   errors loudly when composition is impossible.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.learner import init_train_state, make_learner_step
from distributed_ddpg_tpu.ops import fused_chunk
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.replay.device import DeviceReplay
from distributed_ddpg_tpu.types import pack_batch_np, unpack_batch

OBS, ACT = 5, 3


def _cfg(**kw):
    base = dict(
        actor_hidden=(32, 32),
        critic_hidden=(32, 32),
        batch_size=8,
        fused_chunk="on",  # force the kernel (interpret mode) off-TPU
        seed=3,
    )
    base.update(kw)
    return DDPGConfig(**base)


def _filled_replay(mesh, n=512, capacity=1024, seed=0):
    rng = np.random.default_rng(seed)
    dr = DeviceReplay(capacity, OBS, ACT, mesh=mesh, block_size=128)
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
    return dr


def test_fused_mesh_activates_and_runs_on_data_mesh():
    cfg = _cfg(learner_chunk=4)
    mesh = mesh_lib.make_mesh(data_axis=8, devices=jax.devices())
    lrn = ShardedLearner(cfg, OBS, ACT, action_scale=1.0, mesh=mesh, chunk_size=4)
    assert lrn.fused_mesh_active and lrn.fused_chunk_active
    dr = _filled_replay(lrn.mesh)
    out = lrn.run_sample_chunk(dr)
    # td: [K, global_batch]; scale_batch_with_data default -> 8 * 8 = 64
    assert out.td_errors.shape == (4, 64)
    for v in out.metrics.values():
        assert np.isfinite(float(v))
    # Second chunk exercises the donated steady state.
    out2 = lrn.run_sample_chunk(dr)
    assert np.isfinite(float(out2.metrics["critic_loss"]))


@pytest.mark.slow
def test_fused_mesh_exact_parity_with_local_sgd_reference():
    """The fused-mesh chunk must BE chunk-boundary-averaged local SGD: per
    device d, draws come from fold_in(split(key)[1], d); each device runs
    the kernel-equivalent K scan steps from the shared start state; float
    state is averaged. Reproduce that on the host with make_learner_step
    (already pinned to the kernel by tests/test_fused_chunk.py) and demand
    tight agreement in interpret mode."""
    K, D = 3, 4
    cfg = _cfg()
    mesh = mesh_lib.make_mesh(data_axis=D, devices=jax.devices()[:D])
    lrn = ShardedLearner(cfg, OBS, ACT, action_scale=1.0, mesh=mesh, chunk_size=K)
    assert lrn.fused_mesh_active
    b_local = lrn.global_batch // D
    assert b_local == cfg.batch_size

    dr = _filled_replay(lrn.mesh)
    storage = np.asarray(jax.device_get(dr.device_state()[0]))
    size = int(len(dr))

    out = lrn.run_sample_chunk(dr)

    # --- host reference ---------------------------------------------------
    key = jax.random.PRNGKey(cfg.seed)
    _, sub = jax.random.split(key)
    step = make_learner_step(cfg, 1.0, action_offset=0.0)
    state0 = init_train_state(cfg, OBS, ACT, seed=cfg.seed)
    end_states, tds = [], []
    for d in range(D):
        dkey = jax.random.fold_in(sub, d)
        idx = np.asarray(
            jax.random.randint(dkey, (K, b_local), 0, max(size, 1))
        )
        batches = unpack_batch(jnp.asarray(storage[idx]), OBS, ACT)
        s = state0
        dev_tds = []
        for k in range(K):
            o = jax.jit(step)(s, jax.tree.map(lambda x: x[k], batches))
            s = o.state
            dev_tds.append(np.asarray(o.td_errors))
        end_states.append(s)
        tds.append(np.stack(dev_tds))  # [K, b_local]

    def favg(getter):
        return jax.tree.map(
            lambda *xs: np.mean(np.stack([np.asarray(x) for x in xs]), 0),
            *[getter(s) for s in end_states],
        )

    got = jax.device_get(out.state)
    for getter, got_tree in [
        (lambda s: s.actor_params, got.actor_params),
        (lambda s: s.critic_params, got.critic_params),
        (lambda s: s.target_actor_params, got.target_actor_params),
        (lambda s: s.target_critic_params, got.target_critic_params),
        (lambda s: s.actor_opt.mu, got.actor_opt.mu),
        (lambda s: s.critic_opt.nu, got.critic_opt.nu),
    ]:
        jax.tree.map(
            lambda x, y: np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=2e-4, atol=1e-6
            ),
            favg(getter),
            got_tree,
        )
    # td layout: device-d rows live at columns [d*b_local:(d+1)*b_local].
    ref_td = np.concatenate(tds, axis=1)
    np.testing.assert_allclose(
        ref_td, np.asarray(out.td_errors), rtol=2e-4, atol=1e-5
    )
    # Counts advanced by K, not averaged away.
    assert int(got.actor_opt.count) == K
    assert int(got.step) == K


def _l2_gap(a, b):
    leaves = lambda s: jax.tree.leaves(s.critic_params) + jax.tree.leaves(
        s.actor_params
    )
    return (
        sum(
            float(np.sum((np.asarray(x) - np.asarray(y)) ** 2))
            for x, y in zip(leaves(a), leaves(b))
        )
        ** 0.5
    )


@pytest.mark.slow
def test_fused_mesh_bounded_divergence_vs_scan_path():
    """Local SGD (fused mesh) vs per-step psum (scan path): the two also
    draw DIFFERENT sample streams, so raw parameter distance conflates
    algorithmic divergence with resampling noise. The honest null model is
    the scan path against itself under a different draw seed; the
    cross-algorithm gap must stay within a small factor of that null gap
    (measured here: 1.08 vs null 0.79 at K=8, D=4, 48 steps — local
    averaging adds ~40% on top of resampling noise, far below total
    movement 1.74)."""
    K, D, CHUNKS = 8, 4, 6
    mesh = mesh_lib.make_mesh(data_axis=D, devices=jax.devices()[:D])

    def run(fused, draw_seed=None):
        cfg = _cfg(fused_chunk=fused, actor_lr=1e-3, critic_lr=1e-3)
        lrn = ShardedLearner(
            cfg, OBS, ACT, action_scale=1.0, mesh=mesh, chunk_size=K
        )
        assert lrn.fused_mesh_active == (fused == "on")
        if draw_seed is not None:
            lrn._key = jax.device_put(
                jax.random.PRNGKey(draw_seed), lrn._key.sharding
            )
        dr = _filled_replay(lrn.mesh)
        for _ in range(CHUNKS):
            out = lrn.run_sample_chunk(dr)
            assert np.isfinite(float(out.metrics["critic_loss"]))
        return jax.device_get(lrn.state)

    scan_a = run("off")
    scan_b = run("off", draw_seed=777)
    mesh_a = run("on")
    null_gap = _l2_gap(scan_b, scan_a)
    cross_gap = _l2_gap(mesh_a, scan_a)
    moved = _l2_gap(scan_a, init_train_state(_cfg(), OBS, ACT, seed=3))
    assert null_gap > 0 and moved > 0
    assert cross_gap < 2.0 * null_gap, (cross_gap, null_gap)
    assert cross_gap < moved, (cross_gap, moved)


@pytest.mark.parametrize(
    "extra",
    [
        # One family rides the fast tier (TD3: delayed updates + noise
        # streams, the trickiest schedule); the others run in the slow tier.
        pytest.param(
            dict(distributional=True, num_atoms=21, v_min=-5.0, v_max=5.0),
            marks=pytest.mark.slow,
        ),
        dict(twin_critic=True, policy_delay=2, target_noise=0.2),
        pytest.param(dict(sac=True), marks=pytest.mark.slow),
    ],
    ids=["d4pg", "td3", "sac"],
)
def test_fused_mesh_runs_all_families(extra):
    """The mesh composition must cover every kernel-envelope family: D4PG
    (C51 head in-kernel), TD3 (twin groups + per-device axis-folded
    smoothing noise — each replica draws iid eps), and SAC (axis-folded
    sampling streams + the temperature pmean'd at the chunk boundary)."""
    cfg = _cfg(**extra)
    mesh = mesh_lib.make_mesh(data_axis=4, devices=jax.devices()[:4])
    lrn = ShardedLearner(cfg, OBS, ACT, action_scale=1.0, mesh=mesh, chunk_size=3)
    assert lrn.fused_mesh_active
    dr = _filled_replay(lrn.mesh)
    out = lrn.run_sample_chunk(dr)
    assert out.td_errors.shape == (3, 8 * 4)
    for v in out.metrics.values():
        assert np.isfinite(float(v))
    out2 = lrn.run_sample_chunk(dr)
    assert np.isfinite(float(out2.metrics["critic_loss"]))
    if "twin_critic" in extra:
        # Delay 2 over 6 critic steps -> 3 actor updates, replicas agree.
        assert int(jax.device_get(lrn.state.actor_opt.count)) == 3
        assert int(jax.device_get(lrn.state.critic_opt.count)) == 6
    if "sac" in extra:
        # The learned temperature moved and stayed a replicated scalar.
        la = jax.device_get(lrn.state.log_alpha)
        assert np.isfinite(float(la))
        assert int(jax.device_get(lrn.state.alpha_opt.count)) == 6


def test_fused_mesh_respects_off_and_model_parallel():
    mesh = mesh_lib.make_mesh(data_axis=4, model_axis=2, devices=jax.devices())
    lrn = ShardedLearner(
        _cfg(fused_chunk="auto"), OBS, ACT, action_scale=1.0, mesh=mesh
    )
    assert not lrn.fused_mesh_active and not lrn.fused_chunk_active

    mesh_d = mesh_lib.make_mesh(data_axis=8, devices=jax.devices())
    lrn2 = ShardedLearner(
        _cfg(fused_chunk="auto", fused_mesh="off"),
        OBS, ACT, action_scale=1.0, mesh=mesh_d,
    )
    assert not lrn2.fused_mesh_active and not lrn2.fused_chunk_active
    # Scan path still trains.
    dr = _filled_replay(lrn2.mesh, n=256)
    out = lrn2.run_sample_chunk(dr)
    assert np.isfinite(float(out.metrics["critic_loss"]))

    with pytest.raises(ValueError, match="fused_chunk='on'"):
        ShardedLearner(
            _cfg(fused_chunk="on"), OBS, ACT, action_scale=1.0, mesh=mesh
        )
    with pytest.raises(ValueError, match="fused_chunk='on'"):
        ShardedLearner(
            _cfg(fused_chunk="on", fused_mesh="off"),
            OBS, ACT, action_scale=1.0, mesh=mesh_d,
        )
