"""Parity: the pallas megakernel chunk (ops/fused_chunk.py) must reproduce
the XLA scan path (learner.make_learner_step applied K times) on identical
batches — same params, targets, Adam moments, TD errors, and metrics."""

import jax
import numpy as np
import pytest

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.ops import fused_chunk
from distributed_ddpg_tpu.types import pack_batch_np

OBS, ACT, B, K = 5, 3, 16, 4


def _batches(rng, k):
    return pack_batch_np(
        {
            "obs": rng.standard_normal((k, B, OBS)).astype(np.float32),
            "action": rng.uniform(-1, 1, (k, B, ACT)).astype(np.float32),
            "reward": rng.standard_normal((k, B)).astype(np.float32),
            "discount": np.full((k, B), 0.99, np.float32),
            "next_obs": rng.standard_normal((k, B, OBS)).astype(np.float32),
            "weight": rng.uniform(0.5, 1.0, (k, B)).astype(np.float32),
        }
    )


def _assert_tree_close(a, b, rtol=2e-5, atol=1e-6):
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol
        ),
        a,
        b,
    )


@pytest.mark.parametrize(
    "hidden,scale,offset",
    [
        ((32, 32), 2.0, 0.0),
        # Deeper nets + asymmetric action box: same oracle, second shape —
        # slow tier keeps the fast tier's one-per-branch representative rule.
        pytest.param((32, 24, 16), 1.5, 0.25, marks=pytest.mark.slow),
    ],
)
def test_fused_chunk_matches_scan(hidden, scale, offset):
    """Interpret-mode parity at tight tolerances — the bit-level oracle.
    The same body runs natively compiled on real TPU via tests/tpu_child.py
    (fused_parity_util.assert_fused_matches_scan)."""
    from fused_parity_util import assert_fused_matches_scan

    cfg = DDPGConfig(
        actor_hidden=hidden, critic_hidden=hidden, batch_size=B, seed=3
    )
    assert fused_chunk.supported(cfg)
    assert_fused_matches_scan(
        cfg, OBS, ACT, K, scale, offset,
        interpret=True, rtol=2e-5, atol=1e-6, metric_rtol=5e-5,
    )


def test_fused_chunk_c51_matches_scan():
    """D4PG envelope: the in-kernel categorical projection (triangular-
    kernel accumulation) + closed-form CE/expected-value cotangents must
    reproduce the autodiff scan path at bit-oracle tolerances."""
    from fused_parity_util import assert_fused_matches_scan

    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 24, 16), batch_size=B,
        distributional=True, num_atoms=21, v_min=-5.0, v_max=5.0, seed=3,
    )
    assert fused_chunk.supported(cfg)
    assert_fused_matches_scan(
        cfg, OBS, ACT, K, 1.5, 0.25,
        interpret=True, rtol=2e-4, atol=1e-5, metric_rtol=5e-4,
    )


@pytest.mark.parametrize(
    "distributional",
    [False, pytest.param(True, marks=pytest.mark.slow)],
)
def test_fused_chunk_bf16_matches_scan(distributional):
    """Mixed precision: the kernel's bf16-operand/f32-accumulate dots must
    track the scan path's (models/mlp._dense) within bf16 rounding — the
    two differ only in where autodiff inserts the casts on the backward
    pass, so tolerances are bf16-level, not bit-level."""
    from fused_parity_util import assert_fused_matches_scan

    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=B,
        compute_dtype="bfloat16", distributional=distributional,
        num_atoms=21, v_min=-5.0, v_max=5.0, seed=3,
    )
    assert fused_chunk.supported(cfg)
    assert_fused_matches_scan(
        cfg, OBS, ACT, K, 2.0, 0.0,
        interpret=True, rtol=3e-2, atol=3e-3, metric_rtol=3e-2,
    )


# Both params slow since round 5: the delay=2 leg was the fast tier's
# second-biggest line item (63s interpret-mode compile+run); the TD3
# kernel branch keeps a fast-feedback guard via the scan-path TD3 tests
# and a HARDWARE guard via the runbook's tpu_td3 stage.
@pytest.mark.parametrize(
    "delay,noise",
    [
        pytest.param(1, 0.0, marks=pytest.mark.slow),
        pytest.param(2, 0.2, marks=pytest.mark.slow),
    ],
)
def test_fused_chunk_td3_matches_scan(delay, noise):
    """TD3 in the kernel: twin members as separate rank-2 ref groups,
    min-over-ensemble targets, smoothing noise STREAMED from the scan
    path's exact fold_in(seed, step) draw (bit-comparable), and delayed
    actor/target updates under pl.when with closed-form actor-count
    bookkeeping. The reference scan is also the Adam-count oracle."""
    from fused_parity_util import assert_fused_matches_scan

    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 24, 16), batch_size=B,
        twin_critic=True, policy_delay=delay, target_noise=noise, seed=3,
    )
    assert fused_chunk.supported(cfg)
    assert_fused_matches_scan(
        cfg, OBS, ACT, 5, 1.5, 0.25,
        interpret=True, rtol=2e-4, atol=1e-5, metric_rtol=5e-4,
    )


@pytest.mark.slow
def test_fused_chunk_td3_step_offset_continuity():
    """The delayed-update schedule and the noise stream key off the GLOBAL
    step, so a chunk starting at an arbitrary step0 must keep matching the
    scan path — two consecutive fused chunks vs two scan chunks through
    the public run_sample_chunk API (same draw stream)."""
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.parallel.mesh import make_mesh
    from distributed_ddpg_tpu.replay.device import DeviceReplay

    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=B,
        twin_critic=True, policy_delay=2, target_noise=0.2, seed=5,
    )
    mesh = make_mesh(1, 1, devices=jax.devices()[:1])
    rows = _batches(np.random.default_rng(11), 16).reshape(-1, 2 * OBS + ACT + 3)
    results = {}
    for mode in ("on", "off"):
        lrn = ShardedLearner(
            cfg.replace(fused_chunk=mode), OBS, ACT,
            action_scale=1.0, mesh=mesh, chunk_size=3,  # odd K: step0 drifts
        )
        assert lrn.fused_chunk_active == (mode == "on")
        rep = DeviceReplay(
            capacity=256, obs_dim=OBS, act_dim=ACT, mesh=mesh, block_size=256
        )
        rep.add_packed(rows)
        for _ in range(3):  # chunk boundaries at steps 3, 6 (odd offsets)
            out = lrn.run_sample_chunk(rep)
        results[mode] = (jax.device_get(lrn.state), np.asarray(out.td_errors))
    s_on, td_on = results["on"]
    s_off, td_off = results["off"]
    _assert_tree_close(s_on.critic_params, s_off.critic_params, rtol=5e-4, atol=1e-5)
    _assert_tree_close(s_on.actor_params, s_off.actor_params, rtol=5e-4, atol=1e-5)
    _assert_tree_close(s_on.target_critic_params, s_off.target_critic_params, rtol=5e-4, atol=1e-5)
    np.testing.assert_allclose(td_on, td_off, rtol=5e-4, atol=1e-4)
    assert int(s_on.actor_opt.count) == int(s_off.actor_opt.count)
    assert int(s_on.critic_opt.count) == 9


@pytest.mark.slow
def test_sharded_learner_fused_path_matches_scan_path():
    """On a 1-device mesh, fused_chunk='on' must reproduce fused_chunk='off'
    through the public run_sample_chunk API: both draw the same (K, B) index
    block from the same key stream, so state and TD errors must agree."""
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.parallel.mesh import make_mesh
    from distributed_ddpg_tpu.replay.device import DeviceReplay

    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=B, seed=5
    )
    mesh = make_mesh(1, 1, devices=jax.devices()[:1])
    rng = np.random.default_rng(11)
    rows = pack_batch_np(
        {
            "obs": rng.standard_normal((256, OBS)).astype(np.float32),
            "action": rng.uniform(-1, 1, (256, ACT)).astype(np.float32),
            "reward": rng.standard_normal(256).astype(np.float32),
            "discount": np.full(256, 0.99, np.float32),
            "next_obs": rng.standard_normal((256, OBS)).astype(np.float32),
            "weight": np.ones(256, np.float32),
        }
    )

    results = {}
    for mode in ("on", "off"):
        lrn = ShardedLearner(
            cfg.replace(fused_chunk=mode), OBS, ACT,
            action_scale=1.0, mesh=mesh, chunk_size=K,
        )
        assert lrn.fused_chunk_active == (mode == "on")
        rep = DeviceReplay(
            capacity=256, obs_dim=OBS, act_dim=ACT, mesh=mesh, block_size=256
        )
        rep.add_packed(rows)
        out = lrn.run_sample_chunk(rep)
        results[mode] = (
            jax.device_get(lrn.state),
            np.asarray(out.td_errors),
            {k_: float(v) for k_, v in jax.device_get(out.metrics).items()},
        )

    _assert_tree_close(results["on"][0].actor_params, results["off"][0].actor_params)
    _assert_tree_close(results["on"][0].critic_opt.mu, results["off"][0].critic_opt.mu)
    np.testing.assert_allclose(results["on"][1], results["off"][1], rtol=2e-5, atol=1e-6)
    for k_ in results["on"][2]:
        np.testing.assert_allclose(
            results["on"][2][k_], results["off"][2][k_], rtol=5e-5, atol=1e-6
        )


@pytest.mark.parametrize(
    "fan_in,out,tiles_as_state,tiles_lane_major,held_lane_major",
    [
        (256, 1, 32, 2, True),  # DDPG's critic head at 2x256
        (256, 6, 32, 2, True),  # its actor head
        (300, 51, 38, 21, True),  # the categorical head at 400-300
        (256, 12, 32, 4, True),  # SAC's Gaussian head, [mean | log_std]
        (300, 1, 38, 3, True),  # TD3's critic heads at 400-300
        (16, 21, 2, 3, False),  # wider than deep: [F, out] is the smaller
        (128, 128, 16, 16, False),  # a tie stays as the state has it
    ],
)
def test_output_layer_rides_lane_major_where_that_takes_fewer_tiles(
    fan_in, out, tiles_as_state, tiles_lane_major, held_lane_major
):
    """The shape rule (fused_chunk.lane_major) and the wrapper's two ends:
    _flatten hands the kernel a net's OUTPUT layer as [out, F] where that
    takes fewer (8, 128) tiles, hidden layers and biases as they are, and
    _unflatten (and the twin forms over a [2, ...] ensemble) gives back the
    TrainState's shapes and the same values."""
    assert fused_chunk._tiles(fan_in, out) == tiles_as_state
    assert fused_chunk._tiles(out, fan_in) == tiles_lane_major
    assert fused_chunk.lane_major(fan_in, out) is held_lane_major
    rng = np.random.default_rng(fan_in + out)
    dims = [(406, 300), (300, fan_in), (fan_in, out)]  # [406, 300] alone would flip: hidden layers never do
    params = tuple(
        {
            "w": rng.standard_normal(d).astype(np.float32),
            "b": rng.standard_normal(d[1]).astype(np.float32),
        }
        for d in dims
    )
    flat = fused_chunk._flatten(params)
    held = (out, fan_in) if held_lane_major else (fan_in, out)
    assert [x.shape for x in flat] == [
        (406, 300), (1, 300), (300, fan_in), (1, fan_in), held, (1, out),
    ]
    if held_lane_major:
        np.testing.assert_array_equal(flat[4], params[2]["w"].T)
    back = fused_chunk._unflatten(flat, params)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    jax.tree.map(np.testing.assert_array_equal, back, params)

    twin = jax.tree.map(lambda x: np.stack([x, -x]), params)
    flat2 = fused_chunk._flatten_twin(twin)
    assert [x.shape for x in flat2] == 2 * [x.shape for x in flat]
    back2 = fused_chunk._unflatten_twin(flat2, twin)
    assert jax.tree.structure(back2) == jax.tree.structure(twin)
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), y), back2, twin
    )


@pytest.mark.parametrize(
    "kwargs,tiles",
    [
        ({}, 156),  # DDPG 2x256: 216 with [F, out] heads
        ({"twin_critic": True, "actor_hidden": (400, 300), "critic_hidden": (400, 300)}, 525),  # TD3: 630
        ({"distributional": True, "num_atoms": 51, "actor_hidden": (400, 300), "critic_hidden": (400, 300)}, 367),  # C51: 419
    ],
)
def test_state_tiles_counts_what_the_kernel_holds(kwargs, tiles):
    """The run fact `kernel_state_tiles`, at the three kernel cells' shapes
    (HalfCheetah's 17 / 6): one copy of the parameters, each net's output
    layer in the orientation lane_major picks."""
    assert fused_chunk.state_tiles(DDPGConfig(**kwargs), 17, 6) == tiles


def test_parent_checkpoint_restores_and_continues_on_the_kernel_leg(tmp_path):
    """tests/ckpt_fixtures/parent_pr33 was written by checkpoint.save on the
    tree of commit c3ed97c (before any head rode lane-major): three scan-leg
    updates of a 16-16 DDPG pair at obs 5 / act 3. It restores into a
    kernel-leg learner's state, the kernel continues from it as the scan leg
    does, and what the learner then holds, hands the actors and would
    checkpoint has the parent's shapes."""
    import os
    import shutil

    from distributed_ddpg_tpu import checkpoint as ckpt_lib
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.parallel.mesh import make_mesh
    from distributed_ddpg_tpu.replay.device import DeviceReplay

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ckpt_fixtures", "parent_pr33")
    directory = str(tmp_path / "ckpt")
    shutil.copytree(src, directory)  # restore quarantines in place what fails to verify
    cfg = DDPGConfig(actor_hidden=(16, 16), critic_hidden=(16, 16), batch_size=B, seed=3)
    mesh = make_mesh(1, 1, devices=jax.devices()[:1])
    rows = _batches(np.random.default_rng(11), 16).reshape(-1, 2 * OBS + ACT + 3)
    end = {}
    for mode in ("on", "off"):
        lrn = ShardedLearner(
            cfg.replace(fused_chunk=mode), OBS, ACT, action_scale=1.5,
            action_offset=0.25, mesh=mesh, chunk_size=K,
        )
        assert lrn.fused_chunk_active == (mode == "on")
        assert lrn.kernel_state_tiles == (15 if mode == "on" else None)  # 17 with [F, out] heads
        shapes = jax.tree.map(lambda x: x.shape, lrn.state)
        restored, step, env_steps = ckpt_lib.restore(directory, lrn.state, config=cfg)
        assert (step, env_steps) == (3, 48)
        assert restored.actor_params[-1]["w"].shape == (16, ACT)
        assert restored.critic_opt.nu[-1]["w"].shape == (16, 1)
        assert float(np.abs(restored.critic_opt.nu[-1]["w"]).max()) > 0  # moments the parent's updates left
        lrn.state = jax.device_put(restored, lrn._state_sharding)
        rep = DeviceReplay(capacity=256, obs_dim=OBS, act_dim=ACT, mesh=mesh, block_size=256)
        rep.add_packed(rows)
        lrn.run_sample_chunk(rep)
        assert jax.tree.map(lambda x: x.shape, lrn.state) == shapes
        assert lrn.actor_params_to_host()[-1]["w"].shape == (16, ACT)
        end[mode] = jax.device_get(lrn.state)
    assert int(end["on"].step) == int(end["off"].step) == 3 + K
    assert int(end["on"].critic_opt.count) == 3 + K
    _assert_tree_close(end["on"], end["off"])


def test_auto_mode_kernel_failure_raises(monkeypatch):
    """fused_chunk='auto': whether the kernel runs is decided before
    tracing by stated rules (supported / fits_vmem / runs_native). A
    selected kernel that then dies at first dispatch is an ERROR — the
    learner must not rebind itself to the scan program and carry on
    looking healthy."""
    from distributed_ddpg_tpu.ops import fused_chunk as fc
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.parallel.mesh import make_mesh
    from distributed_ddpg_tpu.replay.device import DeviceReplay

    monkeypatch.setattr(fc, "runs_native", lambda: True)

    def broken_make(*args, **kwargs):
        def run(state, batches, eps=None):
            raise RuntimeError("mosaic boom")

        return run

    monkeypatch.setattr(fc, "make_fused_chunk_fn", broken_make)
    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=B,
        fused_chunk="auto",
    )
    lrn = ShardedLearner(
        cfg, OBS, ACT, action_scale=1.0,
        mesh=make_mesh(1, 1, devices=jax.devices()[:1]), chunk_size=K,
    )
    assert lrn.fused_chunk_active
    rep = DeviceReplay(
        capacity=64, obs_dim=OBS, act_dim=ACT, mesh=lrn.mesh, block_size=64
    )
    rep.add_packed(_batches(np.random.default_rng(3), 4).reshape(-1, rep.width))
    with pytest.raises(RuntimeError, match="mosaic boom"):
        lrn.run_sample_chunk(rep)
    assert lrn.fused_chunk_active  # still the selected program


def test_fused_chunk_on_requires_envelope():
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.parallel.mesh import make_mesh

    with pytest.raises(ValueError):
        ShardedLearner(
            DDPGConfig(critic_l2=1e-4, fused_chunk="on"),
            OBS, ACT, action_scale=1.0,
            mesh=make_mesh(1, 1, devices=jax.devices()[:1]),
        )


def test_supported_gates():
    # D4PG (C51), bf16, and SAC are INSIDE the envelope since round 4.
    assert fused_chunk.supported(DDPGConfig(distributional=True))
    assert fused_chunk.supported(DDPGConfig(compute_dtype="bfloat16"))
    assert fused_chunk.supported(DDPGConfig(sac=True))
    assert fused_chunk.supported(DDPGConfig(sac=True, sac_autotune=False))
    assert not fused_chunk.supported(
        DDPGConfig(distributional=True, num_atoms=512)  # unroll cap
    )
    assert not fused_chunk.supported(DDPGConfig(critic_l2=1e-4))
    assert not fused_chunk.supported(DDPGConfig(action_insert_layer=0))
    assert not fused_chunk.supported(DDPGConfig(critic_hidden=(32,)))
    with pytest.raises(ValueError):
        fused_chunk.make_fused_chunk_fn(
            DDPGConfig(critic_l2=1e-4), OBS, ACT, 1.0
        )
    # VMEM budget gate: huge nets fall back to the XLA scan path.
    big = DDPGConfig(actor_hidden=(1024, 1024), critic_hidden=(1024, 1024))
    assert fused_chunk.supported(big)
    assert not fused_chunk.fits_vmem(big, OBS, ACT)
    with pytest.raises(ValueError, match="VMEM"):
        fused_chunk.make_fused_chunk_fn(big, OBS, ACT, 1.0)
    assert fused_chunk.fits_vmem(DDPGConfig(), 17, 6)  # bench scale fits
    # Config typo guard: only auto/on/off are accepted.
    with pytest.raises(ValueError, match="fused_chunk"):
        DDPGConfig(fused_chunk="Off")


@pytest.mark.parametrize(
    "autotune",
    [
        pytest.param(True, marks=pytest.mark.slow),
        pytest.param(False, marks=pytest.mark.slow),
    ],
)
def test_fused_chunk_sac_matches_scan(autotune):
    """SAC in the kernel (round 4): Gaussian head split + tanh soft-clamp,
    reparameterized sampling from the scan path's exact fold_in stream
    (pre-drawn, streamed like TD3's smoothing noise), entropy-corrected
    twin TD targets, hand-written backward through the squash log-prob,
    and the learned temperature's scalar Adam — all vs the autodiff scan
    path at bit-oracle tolerances. Covers both the learned-alpha and the
    fixed-alpha configurations."""
    from fused_parity_util import assert_fused_matches_scan

    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 24, 16), batch_size=B,
        sac=True, sac_autotune=autotune, seed=3,
    )
    assert fused_chunk.supported(cfg)
    assert_fused_matches_scan(
        cfg, OBS, ACT, K, 1.5, 0.25,
        interpret=True, rtol=2e-4, atol=1e-5, metric_rtol=5e-4,
    )


@pytest.mark.slow
def test_fused_chunk_sac_bf16_matches_scan():
    """SAC x mixed precision: bf16 dots with f32 accumulation on both the
    Gaussian head and the twin critics, bf16-level tolerances."""
    from fused_parity_util import assert_fused_matches_scan

    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=B,
        sac=True, compute_dtype="bfloat16", seed=3,
    )
    assert fused_chunk.supported(cfg)
    assert_fused_matches_scan(
        cfg, OBS, ACT, K, 2.0, 0.0,
        interpret=True, rtol=3e-2, atol=3e-3, metric_rtol=4e-2,
    )


@pytest.mark.slow
def test_fused_chunk_sac_step_offset_continuity():
    """SAC's sampling streams key off the GLOBAL step (fold_in(base,
    step)), so a second fused chunk starting at step0=K must keep matching
    the scan path — run two consecutive chunks through the raw kernel fn
    and the scan, comparing end log_alpha and actor params."""
    from distributed_ddpg_tpu.learner import init_train_state, make_learner_step
    from distributed_ddpg_tpu.types import unpack_batch
    import jax.numpy as jnp

    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=B,
        sac=True, seed=9,
    )
    state = init_train_state(cfg, OBS, ACT, seed=9)
    run = fused_chunk.make_fused_chunk_fn(
        cfg, OBS, ACT, 1.5, 0.25, chunk_size=3, interpret=True
    )
    packed = _batches(np.random.default_rng(13), 6)
    fused = state
    for c in range(2):
        fused, _, _ = jax.jit(run)(fused, jnp.asarray(packed[3 * c : 3 * c + 3]))
    step = make_learner_step(cfg, 1.5, action_offset=0.25)
    ref = state
    for i in range(6):
        ref = step(ref, unpack_batch(jnp.asarray(packed[i]), OBS, ACT)).state
    np.testing.assert_allclose(
        float(fused.log_alpha), float(ref.log_alpha), rtol=2e-4, atol=1e-6
    )
    _assert_tree_close(fused.actor_params, ref.actor_params, rtol=5e-4, atol=1e-5)
    assert int(fused.step) == int(ref.step) == 6


def _spreads(jaxpr, found):
    """(operand shape, result shape) wherever an equation's operand has fewer
    elements than its result: broadcast_in_dim, and the elementwise ops jnp
    hands a size-1 axis to (it leaves that broadcast to the lowering)."""
    for eqn in jaxpr.eqns:
        out = tuple(eqn.outvars[0].aval.shape) if eqn.outvars else None
        if out and eqn.primitive.name not in ("dot_general", "concatenate", "pallas_call"):
            for v in eqn.invars:
                shape = tuple(getattr(v.aval, "shape", ()))
                if len(shape) == len(out) == 2 and shape != out and all(a in (1, b) for a, b in zip(shape, out)):
                    found.append((shape, out))
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else (val,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _spreads(inner, found)
    return found


# the target's softmax 2, the online one's 3, the loss's weights 1, the
# actor pass's softmax and expected value 3: at 21 atoms as at 51
COLUMN_SPREADS_OUTSIDE_THE_LOOP = 9


@pytest.mark.parametrize("atoms", [21, 51])
def test_c51_kernel_body_spreads_no_column_over_the_lanes_per_atom(atoms):
    """The projection's loop over atoms spreads no [B, 1] column over [B, A]:
    on the TPU that is an XLU permute a row tile, and with batch on sublanes
    the loop made two an atom (102 at 51 atoms; 3,264 permutes an update at
    batch 256). With atoms on sublanes it spreads ROWS, [1, B] over [A, B],
    two an atom, and what is left of column spreads in the whole kernel body
    (the three softmaxes' max and sum, the weights) does not grow with the
    atoms."""
    import jax.numpy as jnp

    from distributed_ddpg_tpu.learner import init_train_state

    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=B,
        distributional=True, num_atoms=atoms, v_min=-5.0, v_max=5.0, seed=3,
    )
    column, row = ((B, 1), (B, atoms)), ((1, B), (atoms, B))
    z_col = jnp.zeros((atoms, 1))
    alone = _spreads(
        jax.make_jaxpr(
            lambda p, r: fused_chunk.kernel_projection(p, r, r, z_col, -5.0, 5.0)
        )(jnp.zeros((B, atoms)), jnp.zeros((1, B))).jaxpr,
        [],
    )
    assert alone.count(column) == 0
    assert alone.count(row) == 2 * atoms + 2  # the loop's, and tz from its two rows

    run = fused_chunk.make_fused_chunk_fn(cfg, OBS, ACT, 1.0, chunk_size=2, interpret=True)
    state = init_train_state(cfg, OBS, ACT, seed=3)
    packed = jnp.asarray(_batches(np.random.default_rng(0), 2))
    eqns = [e for e in jax.make_jaxpr(run)(state, packed).jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(eqns) == 1
    body = _spreads(eqns[0].params["jaxpr"], [])
    assert body.count(row) == 2 * atoms + 2
    assert body.count(column) == COLUMN_SPREADS_OUTSIDE_THE_LOOP
