"""Child process for tests/test_tpu.py: runs one native-TPU test case and
prints a JSON result line.

The main test suite pins every test to the virtual CPU platform
(conftest.py), which is exactly how the round-2 megakernel Mosaic bug
escaped: the pallas kernel had only ever compiled in interpret mode
(VERDICT.md round-2 Missing #5). This child runs OUTSIDE that pin — it
lets the platform resolve to the attached TPU — so the
tpu-marked tests exercise real Mosaic compilation, real h2d, and the real
device replay path. Cases:

  probe         -> {"is_tpu": bool, "platform": ..., "device_kind": ...}
  fused_parity  -> native megakernel vs XLA scan path on one chunk
  sample_chunk  -> DeviceReplay ingest + ShardedLearner.run_sample_chunk
                   (the production zero-h2d path), fused kernel active
"""

import json
import os
import sys

# Run as a script: sys.path[0] is tests/, so put the repo root (the package
# parent) ahead of it.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _probe() -> dict:
    import jax

    from distributed_ddpg_tpu.ops.fused_chunk import runs_native

    dev = jax.devices()[0]
    return {
        "is_tpu": runs_native(),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
    }


OBS, ACT, B, K = 17, 6, 64, 8


def _packed(rng, k):
    from distributed_ddpg_tpu.types import pack_batch_np

    return pack_batch_np(
        {
            "obs": rng.standard_normal((k, B, OBS)).astype(np.float32),
            "action": rng.uniform(-1, 1, (k, B, ACT)).astype(np.float32),
            "reward": rng.standard_normal((k, B)).astype(np.float32),
            "discount": np.full((k, B), 0.99, np.float32),
            "next_obs": rng.standard_normal((k, B, OBS)).astype(np.float32),
            "weight": np.ones((k, B), np.float32),
        }
    )


def _fused_parity() -> dict:
    """Natively-compiled megakernel vs the XLA scan path on one chunk — the
    SAME parity body the interpret-mode oracle runs (fused_parity_util),
    at fp-noise tolerances: two different on-TPU programs accumulate in
    different orders."""
    from fused_parity_util import assert_fused_matches_scan

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.ops import fused_chunk

    assert fused_chunk.runs_native(), "fused_parity needs a native TPU backend"
    cfg = DDPGConfig(
        actor_hidden=(256, 256), critic_hidden=(256, 256), batch_size=B, seed=3
    )
    metrics = assert_fused_matches_scan(
        cfg, OBS, ACT, K, 1.0, 0.0,
        interpret=None,  # None = native on TPU (make_fused_chunk_fn default)
        rtol=2e-2, atol=1e-2,
    )
    return {"ok": True, "critic_loss": float(metrics["critic_loss"])}


def _sample_chunk() -> dict:
    """Real h2d ingest into DeviceReplay + the production run_sample_chunk
    dispatch with the megakernel active (fused_chunk defaults to 'auto' and
    must activate on real TPU)."""
    import jax

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.ops import fused_chunk
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.parallel.mesh import make_mesh
    from distributed_ddpg_tpu.replay.device import DeviceReplay

    # Unlike the parity cases this one would run happily on CPU ('auto'
    # selects the scan program there) and print ok:true without ever
    # touching the chip. Assert native like every other case.
    assert fused_chunk.runs_native(), "sample_chunk needs a native TPU backend"
    cfg = DDPGConfig(
        actor_hidden=(256, 256), critic_hidden=(256, 256), batch_size=B
    )
    mesh = make_mesh(1, 1, devices=jax.devices()[:1])
    lrn = ShardedLearner(cfg, OBS, ACT, action_scale=1.0, mesh=mesh, chunk_size=K)
    rep = DeviceReplay(4096, OBS, ACT, mesh=mesh, block_size=1024)
    rng = np.random.default_rng(0)
    rows = _packed(rng, 64).reshape(-1, rep.width)  # 64*B = 4096 rows
    rep.add_packed(rows)
    assert len(rep) == 4096
    out = lrn.run_sample_chunk(rep)
    loss = float(out.metrics["critic_loss"])
    assert np.isfinite(loss)
    assert int(jax.device_get(lrn.state.step)) == K
    out2 = lrn.run_sample_chunk(rep)
    assert np.isfinite(float(out2.metrics["critic_loss"]))
    # ingest_* observability fields ride the native capture (ROADMAP open
    # item: CPU scaling sweeps carried them, TPU captures dropped them) —
    # the REAL h2d ship cost is exactly the number the CPU sweeps can't
    # measure. The snapshot must describe the 4 real 1024-row ships above.
    ingest = rep.ingest_snapshot()
    assert ingest["ingest_ship_calls"] >= 1, ingest
    return {
        "ok": True,
        "fused_chunk_active": lrn.fused_chunk_active,
        "critic_loss": loss,
        **ingest,
    }


def _fused_parity_c51() -> dict:
    """Native Mosaic compile + parity for the D4PG (C51) kernel branch —
    the in-kernel categorical projection and closed-form cotangents."""
    from fused_parity_util import assert_fused_matches_scan

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.ops import fused_chunk

    assert fused_chunk.runs_native(), "needs a native TPU backend"
    cfg = DDPGConfig(
        actor_hidden=(256, 256), critic_hidden=(256, 256), batch_size=B,
        distributional=True, num_atoms=51, v_min=-150.0, v_max=150.0, seed=3,
    )
    metrics = assert_fused_matches_scan(
        cfg, OBS, ACT, K, 1.0, 0.0,
        interpret=None, rtol=2e-2, atol=1e-2,
    )
    return {"ok": True, "critic_loss": float(metrics["critic_loss"])}


def _fused_parity_bf16() -> dict:
    """Native bf16 megakernel (MXU-rate dots, f32 accumulate) vs the bf16
    scan path — bf16-rounding tolerances."""
    from fused_parity_util import assert_fused_matches_scan

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.ops import fused_chunk

    assert fused_chunk.runs_native(), "needs a native TPU backend"
    cfg = DDPGConfig(
        actor_hidden=(256, 256), critic_hidden=(256, 256), batch_size=B,
        compute_dtype="bfloat16", seed=3,
    )
    metrics = assert_fused_matches_scan(
        cfg, OBS, ACT, K, 1.0, 0.0,
        interpret=None, rtol=5e-2, atol=2e-2,
    )
    return {"ok": True, "critic_loss": float(metrics["critic_loss"])}


def _fused_parity_td3() -> dict:
    """Native Mosaic compile + parity for the TD3 kernel branch — twin
    member groups, streamed smoothing noise, pl.when-delayed updates."""
    from fused_parity_util import assert_fused_matches_scan

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.ops import fused_chunk

    assert fused_chunk.runs_native(), "needs a native TPU backend"
    cfg = DDPGConfig(
        actor_hidden=(256, 256), critic_hidden=(256, 256), batch_size=B,
        twin_critic=True, policy_delay=2, target_noise=0.2, seed=3,
    )
    metrics = assert_fused_matches_scan(
        cfg, OBS, ACT, K, 1.0, 0.0,
        interpret=None, rtol=2e-2, atol=1e-2,
    )
    return {"ok": True, "critic_loss": float(metrics["critic_loss"])}


def _fused_parity_sac() -> dict:
    """Native Mosaic compile + parity for the SAC kernel branch — the
    Gaussian-head lane split/concat, streamed sampling normals, squash
    log-prob backward, and the temperature's scalar Adam on (1,1) refs."""
    from fused_parity_util import assert_fused_matches_scan

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.ops import fused_chunk

    assert fused_chunk.runs_native(), "needs a native TPU backend"
    cfg = DDPGConfig(
        actor_hidden=(256, 256), critic_hidden=(256, 256), batch_size=B,
        sac=True, seed=3,
    )
    metrics = assert_fused_matches_scan(
        cfg, OBS, ACT, K, 1.0, 0.0,
        interpret=None, rtol=2e-2, atol=1e-2,
    )
    return {"ok": True, "critic_loss": float(metrics["critic_loss"])}


CASES = {
    "probe": _probe,
    "fused_parity": _fused_parity,
    "fused_parity_c51": _fused_parity_c51,
    "fused_parity_bf16": _fused_parity_bf16,
    "fused_parity_td3": _fused_parity_td3,
    "fused_parity_sac": _fused_parity_sac,
    "sample_chunk": _sample_chunk,
}


if __name__ == "__main__":
    print(json.dumps(CASES[sys.argv[1]]()), flush=True)
