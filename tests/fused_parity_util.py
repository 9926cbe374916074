"""Shared fused-chunk vs XLA-scan parity check, used by BOTH tiers:

- tests/test_fused_chunk.py runs it in pallas interpret mode with tight
  tolerances (the bit-level oracle, no TPU needed), and
- tests/tpu_child.py runs it natively compiled on a real TPU with
  fp-noise tolerances (two different on-TPU programs accumulate in
  different orders).

One body, parameterized by (interpret, tolerances), so the two tiers can
never drift apart semantically.
"""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_ddpg_tpu.learner import (
    chunk_metrics,
    init_train_state,
    make_learner_step,
)
from distributed_ddpg_tpu.ops import fused_chunk
from distributed_ddpg_tpu.types import pack_batch_np, unpack_batch


def make_packed_batches(rng, k: int, b: int, obs: int, act: int):
    return pack_batch_np(
        {
            "obs": rng.standard_normal((k, b, obs)).astype(np.float32),
            "action": rng.uniform(-1, 1, (k, b, act)).astype(np.float32),
            "reward": rng.standard_normal((k, b)).astype(np.float32),
            "discount": np.full((k, b), 0.99, np.float32),
            "next_obs": rng.standard_normal((k, b, obs)).astype(np.float32),
            "weight": rng.uniform(0.5, 1.0, (k, b)).astype(np.float32),
        }
    )


def assert_fused_matches_scan(
    cfg,
    obs: int,
    act: int,
    k: int,
    scale,
    offset,
    interpret: bool | None,
    rtol: float,
    atol: float,
    metric_rtol: float | None = None,
):
    """Run the megakernel chunk and K sequential scan-path steps on the same
    batches; assert end state, TD errors, and the chunk's metrics agree.
    Returns the kernel's metrics dict."""
    state = init_train_state(cfg, obs, act, seed=cfg.seed)
    packed = make_packed_batches(
        np.random.default_rng(7), k, cfg.batch_size, obs, act
    )
    run = fused_chunk.make_fused_chunk_fn(
        cfg, obs, act, scale, offset, chunk_size=k, interpret=interpret
    )
    new_state, td, metrics = jax.jit(run)(state, jnp.asarray(packed))

    step = make_learner_step(cfg, scale, action_offset=offset)
    ref = state
    ref_tds, ref_ms = [], []
    for i in range(k):
        out = step(ref, unpack_batch(jnp.asarray(packed[i]), obs, act))
        ref = out.state
        ref_tds.append(np.asarray(out.td_errors))
        ref_ms.append(out.metrics)

    def close(a, b):
        jax.tree.map(
            lambda x, y: np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=rtol, atol=atol
            ),
            a,
            b,
        )

    close(new_state.actor_params, ref.actor_params)
    close(new_state.critic_params, ref.critic_params)
    close(new_state.target_actor_params, ref.target_actor_params)
    close(new_state.target_critic_params, ref.target_critic_params)
    close(new_state.actor_opt.mu, ref.actor_opt.mu)
    close(new_state.critic_opt.nu, ref.critic_opt.nu)
    # The other two moment trees: the kernel may hold a net's output layer
    # lane-major (fused_chunk.lane_major), so its target and both moments
    # are held to the TrainState's [F, out] (a transposed leaf fails on its
    # shape) and values too, not only its weights.
    close(new_state.actor_opt.nu, ref.actor_opt.nu)
    close(new_state.critic_opt.mu, ref.critic_opt.mu)
    # The reference scan IS the count oracle: TD3's delayed actor updates
    # advance actor_opt.count less often than the critic's.
    assert int(new_state.actor_opt.count) == int(ref.actor_opt.count)
    assert int(new_state.critic_opt.count) == int(ref.critic_opt.count) == k
    assert int(new_state.step) == k
    if cfg.sac:
        # SAC: the in-kernel temperature must track the scan path's.
        close(new_state.log_alpha, ref.log_alpha)
        if cfg.sac_autotune:
            close(new_state.alpha_opt.mu, ref.alpha_opt.mu)
            close(new_state.alpha_opt.nu, ref.alpha_opt.nu)
            assert int(new_state.alpha_opt.count) == int(
                ref.alpha_opt.count
            ) == k
    np.testing.assert_allclose(
        np.asarray(td), np.stack(ref_tds), rtol=rtol, atol=atol
    )
    m_rtol = metric_rtol if metric_rtol is not None else rtol
    # the chunk's reduction of its K updates' metrics, as the scan chunk
    # makes it (mean; `c51_edge_mass` is the last update's)
    want = chunk_metrics(
        {k: jnp.stack([m[k] for m in ref_ms]) for k in ref_ms[0]}
    )
    for name in metrics:
        np.testing.assert_allclose(
            float(metrics[name]), float(want[name]), rtol=m_rtol, atol=atol
        )
    return metrics
