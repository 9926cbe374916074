"""Device-resident prioritized replay (replay/device.py
DevicePrioritizedReplay + parallel/learner.py run_sample_chunk_per):
distribution parity against the host sum-tree semantics, IS-weight formula
parity, the fused chunk end-to-end, and checkpoint roundtrip."""

import jax
import jax.numpy as jnp
import numpy as np

import pytest

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.parallel.mesh import make_mesh
from distributed_ddpg_tpu.replay.device import (
    DevicePrioritizedReplay,
    draw_per_indices,
)
from distributed_ddpg_tpu.types import pack_batch_np


def _packed_rows(n, width, seed=0):
    rng = np.random.default_rng(seed)
    return (0.1 * rng.standard_normal((n, width))).astype(np.float32)


def test_draw_per_indices_proportional_and_weights():
    """Empirical frequency of the stratified inverse-CDF draw must match
    p_i / sum(p) (the defining property of proportional PER, same as the
    host SumTree.stratified_sample), and the IS weights must equal the
    host formula (N * P(i))^-beta / max."""
    cap = 64
    rng = np.random.default_rng(0)
    prios = np.zeros(cap, np.float32)
    n = 48
    prios[:n] = rng.uniform(0.1, 2.0, n).astype(np.float32)
    probs = prios / prios.sum()

    k, b, draws = 25, 64, 40
    counts = np.zeros(cap)
    beta = 0.7
    for d in range(draws):
        idx, w = jax.jit(draw_per_indices, static_argnums=3)(
            jax.random.PRNGKey(d), jnp.asarray(prios), jnp.int32(n),
            (k, b), jnp.float32(beta),
        )
        idx = np.asarray(idx)
        counts += np.bincount(idx.reshape(-1), minlength=cap)
        # IS weights: host formula on the same indices.
        w_host = (n * probs[idx]) ** (-beta)
        w_host = w_host / w_host.max(axis=-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(w), w_host, rtol=2e-4)

    freq = counts / counts.sum()
    # 64k total draws: proportional to priorities within a few percent.
    np.testing.assert_allclose(freq[:n], probs[:n], atol=0.004)
    assert counts[n:].sum() == 0, "sampled beyond the fill"


def test_device_per_insert_stamps_max_priority():
    mesh = make_mesh(-1, 1)
    rep = DevicePrioritizedReplay(512, 4, 2, mesh=mesh, block_size=64)
    rep.add_packed(_packed_rows(128, rep.width))
    assert len(rep) == 128
    prios = np.asarray(jax.device_get(rep.priorities))
    np.testing.assert_allclose(prios[:128], 1.0)  # initial max priority
    np.testing.assert_allclose(prios[128:], 0.0)  # empty slots zero-mass


@pytest.mark.slow
def test_run_sample_chunk_per_updates_priorities():
    cfg = DDPGConfig(
        actor_hidden=(16, 16), critic_hidden=(16, 16), batch_size=16,
        prioritized=True, fused_chunk="off", seed=0,
    )
    mesh = make_mesh(-1, 1)
    learner = ShardedLearner(cfg, 4, 2, action_scale=1.0, mesh=mesh,
                             chunk_size=4)
    rep = DevicePrioritizedReplay(1024, 4, 2, mesh=mesh, block_size=64,
                                  alpha=cfg.per_alpha, eps=cfg.per_eps)
    rep.add_packed(_packed_rows(256, rep.width))

    before = np.asarray(jax.device_get(rep.priorities)).copy()
    out = learner.run_sample_chunk_per(rep, beta=0.5)
    assert np.isfinite(float(out.metrics["critic_loss"]))
    assert int(jax.device_get(learner.state.step)) == 4

    after = np.asarray(jax.device_get(rep.priorities))
    changed = np.flatnonzero(before[:256] != after[:256])
    # 4 steps x 16 samples = 64 draws; duplicates allowed but most land.
    assert len(changed) >= 16, f"only {len(changed)} priorities updated"
    # Updated priorities follow (|td| + eps)^alpha — strictly positive and
    # not the insert stamp value.
    assert np.all(after[:256] > 0)
    # Second chunk keeps working with the updated vector (beta annealed).
    out2 = learner.run_sample_chunk_per(rep, beta=0.9)
    assert np.isfinite(float(out2.metrics["critic_loss"]))
    assert int(jax.device_get(learner.state.step)) == 8


def test_device_per_checkpoint_roundtrip(tmp_path):
    from distributed_ddpg_tpu import checkpoint as ckpt_lib
    from distributed_ddpg_tpu.learner import init_train_state

    cfg = DDPGConfig(actor_hidden=(16, 16), critic_hidden=(16, 16),
                     prioritized=True)
    state = init_train_state(cfg, 4, 2, seed=0)
    mesh = make_mesh(-1, 1)
    rep = DevicePrioritizedReplay(256, 4, 2, mesh=mesh, block_size=32)
    rep.add_packed(_packed_rows(96, rep.width))
    # Perturb priorities so the roundtrip carries non-trivial values.
    rep.set_per_state(
        rep.priorities.at[:96].set(jnp.linspace(0.2, 3.0, 96)),
        jnp.float32(3.0),
    )
    ckpt_lib.save(str(tmp_path), 11, state, rep, cfg)

    fresh = DevicePrioritizedReplay(256, 4, 2, mesh=mesh, block_size=32)
    template = init_train_state(cfg, 4, 2, seed=5)
    _, step, _ = ckpt_lib.restore(str(tmp_path), template, fresh)
    assert step == 11 and len(fresh) == 96
    np.testing.assert_allclose(
        np.asarray(jax.device_get(fresh.priorities))[:96],
        np.linspace(0.2, 3.0, 96), rtol=1e-6,
    )
    assert float(jax.device_get(fresh.max_priority)) == 3.0
    np.testing.assert_allclose(
        np.asarray(jax.device_get(fresh.storage))[:96],
        np.asarray(jax.device_get(rep.storage))[:96],
    )


@pytest.mark.slow
def test_fused_per_matches_scan_per():
    """PER x megakernel (round 4): with fused_chunk='on' the PER chunk runs
    the kernel (draw + priority scatter stay XLA ops, IS weights ride the
    packed weight column); same key stream -> identical draws -> the end
    state, TD errors, metrics, AND the updated priority vector must match
    the scan path at interpret-oracle tolerances. Covers DDPG, D4PG, and
    SAC (round-4 kernel envelope)."""
    for extra in (
        {},
        dict(distributional=True, num_atoms=21, v_min=-5.0, v_max=5.0),
        dict(sac=True),
    ):
        results = {}
        for mode in ("on", "off"):
            cfg = DDPGConfig(
                actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=16,
                prioritized=True, fused_chunk=mode, seed=7, **extra,
            )
            mesh = make_mesh(1, 1, devices=jax.devices()[:1])
            lrn = ShardedLearner(
                cfg, 4, 2, action_scale=1.0, mesh=mesh, chunk_size=4
            )
            assert lrn.fused_per_active == (mode == "on")
            rep = DevicePrioritizedReplay(
                512, 4, 2, mesh=mesh, block_size=64,
                alpha=cfg.per_alpha, eps=cfg.per_eps,
            )
            rep.add_packed(_packed_rows(256, rep.width))
            out = lrn.run_sample_chunk_per(rep, beta=0.5)
            results[mode] = (
                jax.device_get(lrn.state),
                np.asarray(out.td_errors),
                {k: float(v) for k, v in jax.device_get(out.metrics).items()},
                np.asarray(jax.device_get(rep.priorities)),
            )
        s_on, td_on, m_on, p_on = results["on"]
        s_off, td_off, m_off, p_off = results["off"]
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
            ),
            s_on.critic_params, s_off.critic_params,
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
            ),
            s_on.actor_opt.mu, s_off.actor_opt.mu,
        )
        np.testing.assert_allclose(td_on, td_off, rtol=2e-4, atol=1e-5)
        np.testing.assert_allclose(p_on, p_off, rtol=2e-4, atol=1e-6)
        for k in m_on:
            np.testing.assert_allclose(m_on[k], m_off[k], rtol=5e-4, atol=1e-6)
