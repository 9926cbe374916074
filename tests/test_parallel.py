"""Sharded-learner tests on the virtual 8-device CPU mesh (SURVEY.md §4
'Distributed without a cluster'): auto (jit+sharding) vs explicit
(shard_map+pmean) vs single-device reference — all must agree; TP sharding
must actually partition params; the scan chunk must equal K single steps."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.learner import init_train_state, jit_learner_step
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.parallel.prefetch import ChunkPrefetcher
from distributed_ddpg_tpu.replay import UniformReplay
from distributed_ddpg_tpu.types import batch_from_numpy

OBS, ACT, B = 4, 2, 64


def _cfg(**kw):
    base = dict(actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=B, seed=0)
    base.update(kw)
    return DDPGConfig(**base)


def _np_batch(rng, b=B):
    return {
        "obs": rng.standard_normal((b, OBS)).astype(np.float32),
        "action": rng.uniform(-1, 1, (b, ACT)).astype(np.float32),
        "reward": rng.standard_normal(b).astype(np.float32),
        "discount": np.full(b, 0.99, np.float32),
        "next_obs": rng.standard_normal((b, OBS)).astype(np.float32),
        "weight": np.ones(b, np.float32),
    }


def test_mesh_shapes():
    assert len(jax.devices()) == 8, "conftest should provide 8 fake CPU devices"
    m = mesh_lib.make_mesh(-1, 1)
    assert m.shape == {"data": 8, "model": 1}
    m = mesh_lib.make_mesh(-1, 2)
    assert m.shape == {"data": 4, "model": 2}
    with pytest.raises(ValueError):
        mesh_lib.make_mesh(3, 2)


@pytest.mark.parametrize("mode", ["auto", "explicit"])
def test_sharded_matches_single_device(mode):
    cfg = _cfg()
    rng = np.random.default_rng(0)
    batches = [_np_batch(rng) for _ in range(4)]

    ref_state = init_train_state(cfg, OBS, ACT, seed=0)
    ref_step = jit_learner_step(cfg, 1.0, donate=False)
    for nb in batches:
        ref_out = ref_step(ref_state, batch_from_numpy(nb))
        ref_state = ref_out.state

    lrn = ShardedLearner(cfg, OBS, ACT, action_scale=1.0, mode=mode)
    for nb in batches:
        out = lrn.step(nb)
    np.testing.assert_allclose(
        float(out.metrics["critic_loss"]), float(ref_out.metrics["critic_loss"]),
        rtol=1e-4,
    )
    for a, b in zip(
        jax.tree.leaves(jax.device_get(lrn.state.actor_params)),
        jax.tree.leaves(jax.device_get(ref_state.actor_params)),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        np.sort(np.asarray(out.td_errors)),
        np.sort(np.asarray(ref_out.td_errors)),
        rtol=1e-3, atol=1e-5,
    )


def test_tensor_parallel_params_actually_sharded():
    cfg = _cfg(model_axis=2)
    lrn = ShardedLearner(cfg, OBS, ACT, action_scale=1.0)
    # Layer 0 kernel (OBS x 32) should be column-parallel over 'model'.
    spec = lrn.state.actor_params[0]["w"].sharding.spec
    assert spec == P(None, "model")
    # And a step must still run + stay finite.
    out = lrn.step(_np_batch(np.random.default_rng(1)))
    assert np.isfinite(float(out.metrics["critic_loss"]))


def test_tp_matches_dp_numerically():
    cfg_dp = _cfg(model_axis=1)
    cfg_tp = _cfg(model_axis=2)
    rng = np.random.default_rng(2)
    batches = [_np_batch(rng) for _ in range(3)]
    lrn_dp = ShardedLearner(cfg_dp, OBS, ACT, action_scale=1.0)
    lrn_tp = ShardedLearner(cfg_tp, OBS, ACT, action_scale=1.0)
    for nb in batches:
        out_dp = lrn_dp.step(nb)
        out_tp = lrn_tp.step(nb)
    np.testing.assert_allclose(
        float(out_tp.metrics["critic_loss"]),
        float(out_dp.metrics["critic_loss"]),
        rtol=1e-4,
    )
    for a, b in zip(
        jax.tree.leaves(jax.device_get(lrn_tp.state.critic_params)),
        jax.tree.leaves(jax.device_get(lrn_dp.state.critic_params)),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_chunk_equals_k_single_steps():
    cfg = _cfg()
    rng = np.random.default_rng(3)
    batches = [_np_batch(rng) for _ in range(5)]
    lrn_a = ShardedLearner(cfg, OBS, ACT, action_scale=1.0)
    for nb in batches:
        lrn_a.step(nb)
    lrn_b = ShardedLearner(cfg, OBS, ACT, action_scale=1.0)
    stacked = {k: np.stack([nb[k] for nb in batches]) for k in batches[0]}
    out = lrn_b.run_chunk(stacked)
    assert np.asarray(out.td_errors).shape == (5, B)
    for a, b in zip(
        jax.tree.leaves(jax.device_get(lrn_a.state)),
        jax.tree.leaves(jax.device_get(lrn_b.state)),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6)


def test_prefetcher_feeds_chunks():
    cfg = _cfg(replay_capacity=1024)
    replay = UniformReplay(1024, OBS, ACT, seed=0)
    rng = np.random.default_rng(4)
    nb = _np_batch(rng, b=512)
    replay.add_batch(nb["obs"], nb["action"], nb["reward"], nb["discount"], nb["next_obs"])
    lrn = ShardedLearner(cfg, OBS, ACT, action_scale=1.0)
    pf = ChunkPrefetcher(replay, lrn.put_chunk, batch_size=B, chunk_size=4, depth=2).start()
    try:
        for _ in range(3):
            chunk, indices = pf.next(timeout=30)
            assert indices.shape == (4, B)
            out = lrn.run_chunk_async(chunk)
            assert np.isfinite(float(out.metrics["critic_loss"]))
    finally:
        pf.stop()


def test_compile_cache_is_placed_from_outside(tmp_path):
    """One rule (parallel/mesh.py import): JAX_COMPILATION_CACHE_DIR set ->
    the code assigns nothing and JAX uses that directory; unset -> the
    same <checkout>/.jax_cache from any process and working directory; a
    process that asked for the CPU gets no default. Importing the module
    initialises no backend, so the children need no chip."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import jax; from distributed_ddpg_tpu.parallel import mesh; "
        "print(jax.config.jax_compilation_cache_dir)"
    )
    base = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")
    }
    base["PYTHONPATH"] = root
    cases = {
        "env": ({**base, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "c")}, root),
        "here": (base, root),
        "elsewhere": (base, str(tmp_path)),
        "cpu": ({**base, "JAX_PLATFORMS": "cpu"}, root),
    }
    procs = {
        name: subprocess.Popen(
            [sys.executable, "-c", code], env=env, cwd=cwd,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for name, (env, cwd) in cases.items()
    }
    got = {}
    for name, proc in procs.items():
        out, err = proc.communicate(timeout=120)
        assert proc.returncode == 0, (name, err)
        got[name] = out.strip().splitlines()[-1]
    assert got["env"] == str(tmp_path / "c")
    assert got["here"] == got["elsewhere"] == os.path.join(root, ".jax_cache")
    assert got["cpu"] == "None"


def test_multihost_noop_single_process():
    from distributed_ddpg_tpu.parallel import multihost

    assert multihost.initialize() is False
    info = multihost.process_info()
    assert info["process_count"] == 1 and info["global_device_count"] == 8


def test_prefetcher_surfaces_worker_exception_promptly():
    class BoomReplay:
        def sample(self, n):
            raise RuntimeError("boom")

    cfg = _cfg()
    lrn = ShardedLearner(cfg, OBS, ACT, action_scale=1.0)
    pf = ChunkPrefetcher(BoomReplay(), lrn.put_chunk, B, 2, depth=2).start()
    import time as _time

    t0 = _time.time()
    with pytest.raises(RuntimeError, match="prefetch thread died"):
        pf.next(timeout=30)
    assert _time.time() - t0 < 5, "exception should surface promptly, not on timeout"
    pf.stop()


@pytest.mark.parametrize(
    "scaled,want_batch",
    [pytest.param(True, 4 * B, marks=pytest.mark.slow), (False, B)],
)
def test_scale_batch_with_data(scaled, want_batch):
    """Per-device batch semantics (config.scale_batch_with_data): on a
    4-device data mesh the sampling paths draw batch_size rows PER DEVICE
    (global batch 4B), so adding chips adds throughput instead of slicing
    a fixed 64 rows thinner; False preserves the fixed-global semantics."""
    from distributed_ddpg_tpu.replay.device import (
        DevicePrioritizedReplay,
        DeviceReplay,
    )
    from distributed_ddpg_tpu.types import pack_batch_np

    cfg = _cfg(scale_batch_with_data=scaled)
    mesh = mesh_lib.make_mesh(4, 1, devices=jax.devices()[:4])
    K = 3
    lrn = ShardedLearner(cfg, OBS, ACT, action_scale=1.0, mesh=mesh, chunk_size=K)
    assert lrn.global_batch == want_batch
    rng = np.random.default_rng(0)
    rows = pack_batch_np(_np_batch(rng, b=2048))
    rep = DeviceReplay(4096, OBS, ACT, mesh=mesh, block_size=1024)
    rep.add_packed(rows)
    out = lrn.run_sample_chunk(rep)
    assert out.td_errors.shape == (K, want_batch)
    assert np.isfinite(float(out.metrics["critic_loss"]))

    per = DevicePrioritizedReplay(4096, OBS, ACT, mesh=mesh, block_size=1024)
    per.add_packed(rows)
    out = lrn.run_sample_chunk_per(per, beta=0.5)
    assert out.td_errors.shape == (K, want_batch)
    assert np.isfinite(float(out.metrics["critic_loss"]))


# --- the scan chunk on a data mesh (PR 45): the programs a mesh gets are the
# algorithm a single chip runs, and only a mesh on the chip is compiled with
# the TPU compiler's own options ---

SAC_FAMILIES = {
    "sac": dict(sac=True),
    # the policy's half, its all-reduce with it, under the `cond`
    "redq": dict(sac=True, critic_ensemble=3, target_subset=2, policy_delay=2),
}


@pytest.mark.parametrize("family", SAC_FAMILIES)
def test_mesh_scan_chunk_equals_k_single_steps_and_the_one_device_learner(family):
    """K updates in one launch of the 4x1 mesh's scan chunk, against the
    same K as single steps on the mesh and as one chunk of the one-device
    learner on the same global batch: one algorithm, whatever reduces."""
    cfg = _cfg(**SAC_FAMILIES[family])
    K = 4
    rng = np.random.default_rng(7)
    batches = [_np_batch(rng) for _ in range(K)]
    stacked = {k: np.stack([nb[k] for nb in batches]) for k in batches[0]}
    four = mesh_lib.make_mesh(4, 1, devices=jax.devices()[:4])
    one = mesh_lib.make_mesh(1, 1, devices=jax.devices()[:1])

    chunked = ShardedLearner(cfg, OBS, ACT, action_scale=1.0, mesh=four, chunk_size=K)
    assert chunked.data_size == 4 and not chunked.fused_chunk_active
    out = chunked.run_chunk(stacked)
    assert np.asarray(out.td_errors).shape == (K, B)
    stepped = ShardedLearner(cfg, OBS, ACT, action_scale=1.0, mesh=four)
    for nb in batches:
        stepped.step(nb)
    alone = ShardedLearner(cfg, OBS, ACT, action_scale=1.0, mesh=one, chunk_size=K)
    out_alone = alone.run_chunk(stacked)

    assert int(chunked.state.step) == K
    # the policy stepped on the updates the rule names, on every replica alike
    assert int(chunked.state.actor_opt.count) == -(-K // cfg.policy_delay)
    for other in (stepped, alone):
        for a, b in zip(
            jax.tree.leaves(jax.device_get(chunked.state)),
            jax.tree.leaves(jax.device_get(other.state)),
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(out.td_errors), np.asarray(out_alone.td_errors), rtol=1e-3, atol=1e-5
    )
    np.testing.assert_allclose(
        float(out.metrics["critic_loss"]), float(out_alone.metrics["critic_loss"]), rtol=1e-4
    )


def test_mesh_compiler_options_rule():
    from distributed_ddpg_tpu.parallel.learner import mesh_compiler_options

    assert mesh_compiler_options(1, True) is None  # one chip: nothing to reduce over
    assert mesh_compiler_options(4, False) is None  # XLA:CPU knows none of the names
    assert mesh_compiler_options(1, False) is None
    on = mesh_compiler_options(4, True)
    assert on == {
        "xla_enable_async_all_reduce": True,
        "xla_tpu_enable_async_collective_fusion_fuse_all_reduce": True,
    }
    on.clear()  # a copy: a caller cannot edit the rule
    assert mesh_compiler_options(2, True)


@pytest.mark.parametrize("devices,native,optioned", [
    (4, True, True),  # a data mesh on the chip
    (4, False, False),  # this CPU mesh: every program builds as it did
    (1, True, False),  # one chip: jit is handed what it was handed before
])
def test_only_a_data_mesh_on_the_chip_hands_jit_compile_options(monkeypatch, devices, native, optioned):
    """What _build_programs hands jax.jit, program by program (jit is lazy:
    nothing compiles, so the TPU's option names meet no compiler here)."""
    from distributed_ddpg_tpu.ops import fused_chunk
    from distributed_ddpg_tpu.parallel import learner as learner_lib

    monkeypatch.setattr(fused_chunk, "runs_native", lambda: native)
    handed = {}
    jit = jax.jit

    def recording(fn, **kw):
        handed[fn.__name__] = kw.get("compiler_options")
        return jit(fn, **kw)

    monkeypatch.setattr(learner_lib.jax, "jit", recording)
    mesh = mesh_lib.make_mesh(devices, 1, devices=jax.devices()[:devices])
    lrn = ShardedLearner(
        _cfg(sac=True, fused_chunk="off"), OBS, ACT, action_scale=1.0, mesh=mesh, chunk_size=2
    )
    monkeypatch.setattr(learner_lib.jax, "jit", jit)
    assert not lrn.fused_chunk_active
    chunks = {"chunk_fn", "sample_chunk_fn", "per_sample_chunk_fn"}
    assert chunks <= set(handed) and "packed_step" in handed
    want = learner_lib.mesh_compiler_options(devices, native)
    assert (want is not None) == optioned
    for name in chunks:
        assert handed[name] == want, name
    assert handed["packed_step"] is None  # the single step is no scan chunk
