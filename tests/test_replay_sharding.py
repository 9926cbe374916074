"""Sharded device replay (replay_sharding='sharded'; replay/device.py,
docs/REPLAY_SHARDING.md): the ISSUE-10 acceptance suite.

Replicated mode is the bit-exact oracle: the sharded placement must land
the same logical ring (same ptr/size/contents), draw the same sample
stream from the same key, and produce bit-identical learner chunks —
while measurably landing ~1/N ingest bytes per row and holding ~1/N
storage bytes per device (asserted here against the measured
`replay_*` counters the train records carry)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.parallel import multihost
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.parallel.mesh import make_mesh
from distributed_ddpg_tpu.replay.device import (
    DevicePrioritizedReplay,
    DeviceReplay,
    make_sharded_per_draw,
)
from distributed_ddpg_tpu.types import pack_batch_np, packed_width

OBS, ACT, B = 4, 2, 64
W = packed_width(OBS, ACT)


def _rows(rng, n):
    return pack_batch_np(
        {
            "obs": rng.standard_normal((n, OBS)).astype(np.float32),
            "action": rng.uniform(-1, 1, (n, ACT)).astype(np.float32),
            "reward": rng.standard_normal(n).astype(np.float32),
            "discount": np.full(n, 0.99, np.float32),
            "next_obs": rng.standard_normal((n, OBS)).astype(np.float32),
            "weight": np.ones(n, np.float32),
        }
    )


def _pair(cls, mesh, capacity=256, block=64, **kw):
    return {
        mode: cls(capacity, OBS, ACT, mesh=mesh, block_size=block,
                  replay_sharding=mode, **kw)
        for mode in ("replicated", "sharded")
    }


# --------------------------------------------------------------------------
# ingest parity: same stream -> same logical ring (incl. wraparound)
# --------------------------------------------------------------------------


def test_sharded_ingest_matches_replicated_through_wraparound():
    mesh = make_mesh(-1, 1)
    reps = _pair(DeviceReplay, mesh)
    rng = np.random.default_rng(0)
    blocks = [_rows(rng, 64) for _ in range(5)]  # 320 rows > capacity 256
    for rep in reps.values():
        for b in blocks:
            rep.add_packed(b.copy())
    sa, sb = reps["replicated"].state_dict(), reps["sharded"].state_dict()
    assert int(sa["ptr"]) == int(sb["ptr"]) == 64
    assert int(sa["size"]) == int(sb["size"]) == 256
    np.testing.assert_array_equal(sa["packed"], sb["packed"])


def test_sharded_ingest_lands_one_copy_per_row():
    """The measured-bytes acceptance: with N simulated devices the sharded
    placement must land <= (replicated bytes / N) * 1.1 per ingested row
    and hold ~1/N storage bytes per device (~N x aggregate capacity)."""
    mesh = make_mesh(-1, 1)
    n_dev = mesh.shape["data"]
    assert n_dev == 8  # conftest pins 8 virtual devices
    reps = _pair(DeviceReplay, mesh, capacity=1024, block=128)
    rng = np.random.default_rng(1)
    for rep in reps.values():
        rep.add_packed(_rows(rng, 512))
    snap = {m: r.ingest_snapshot() for m, r in reps.items()}
    repl = snap["replicated"]["replay_ingest_bytes_per_row"]
    shard = snap["sharded"]["replay_ingest_bytes_per_row"]
    assert repl > 0 and shard > 0
    assert shard <= (repl / n_dev) * 1.1, (shard, repl)
    assert snap["sharded"]["replay_shard_count"] == n_dev
    assert (
        snap["replicated"]["replay_device_storage_bytes"]
        >= 0.9 * n_dev * snap["sharded"]["replay_device_storage_bytes"]
    )
    # Strided ownership keeps per-shard fill balanced within one row.
    assert (
        snap["sharded"]["replay_shard_fill_max"]
        - snap["sharded"]["replay_shard_fill_min"]
    ) <= 1


# --------------------------------------------------------------------------
# sampling parity oracle: same key -> bit-identical minibatches/chunks
# --------------------------------------------------------------------------


def test_sampling_parity_oracle_uniform_chunk_bit_identical():
    """ISSUE-10 acceptance: same ingest stream + same sampling key =>
    identical sampled minibatches. The strided placement preserves every
    logical position, the index draw is replica-identical, and the
    masked-gather + psum exchange adds exact zeros — so the WHOLE chunk
    (td errors, metrics, updated params) is bit-identical, not merely
    close."""
    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=B,
        seed=0, fused_chunk="off",
    )
    rng = np.random.default_rng(2)
    data = _rows(rng, 512)
    outs = {}
    for mode in ("replicated", "sharded"):
        lrn = ShardedLearner(cfg, OBS, ACT, action_scale=1.0, chunk_size=4,
                             replay_sharding=mode)
        rep = DeviceReplay(1024, OBS, ACT, mesh=lrn.mesh, block_size=256,
                           replay_sharding=mode)
        rep.add_packed(data.copy())
        out = lrn.run_sample_chunk(rep)
        outs[mode] = (
            np.asarray(out.td_errors),
            {k: float(v) for k, v in jax.device_get(out.metrics).items()},
            jax.device_get(lrn.state.actor_params),
        )
    np.testing.assert_array_equal(outs["replicated"][0], outs["sharded"][0])
    assert outs["replicated"][1] == outs["sharded"][1]
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        outs["replicated"][2], outs["sharded"][2],
    )


# --------------------------------------------------------------------------
# PER: stamp parity is exact; the two-level draw matches distributionally
# --------------------------------------------------------------------------


def test_per_stamp_parity_and_checkpoint_roundtrip():
    mesh = make_mesh(-1, 1)
    pers = _pair(DevicePrioritizedReplay, mesh)
    rng = np.random.default_rng(3)
    blocks = [_rows(rng, 64) for _ in range(3)]
    for per in pers.values():
        for b in blocks:
            per.add_packed(b.copy())
    pa, pb = pers["replicated"].state_dict(), pers["sharded"].state_dict()
    np.testing.assert_array_equal(pa["packed"], pb["packed"])
    np.testing.assert_array_equal(pa["priorities"], pb["priorities"])
    # Checkpoint wire format is placement-independent: a replicated
    # state_dict loads into a sharded buffer (and back) bit-exactly.
    fresh = DevicePrioritizedReplay(
        256, OBS, ACT, mesh=mesh, block_size=64, replay_sharding="sharded"
    )
    fresh.load_state_dict(pa)
    np.testing.assert_array_equal(
        fresh.state_dict()["priorities"], pa["priorities"]
    )
    np.testing.assert_array_equal(fresh.state_dict()["packed"], pa["packed"])


def test_sharded_per_draw_is_proportional():
    """Two-level sampler sanity: a row holding ~all the priority mass must
    dominate the draw, and every drawn index must be a live row."""
    mesh = make_mesh(-1, 1)
    per = DevicePrioritizedReplay(
        256, OBS, ACT, mesh=mesh, block_size=64, replay_sharding="sharded"
    )
    rng = np.random.default_rng(4)
    per.add_packed(_rows(rng, 192))
    # Overwrite priorities host-side: row 37 gets 1e4, everyone else 1.
    st = per.state_dict()
    st["priorities"] = np.ones(192, np.float32)
    st["priorities"][37] = 1e4
    per.load_state_dict(st)
    draw = make_sharded_per_draw(mesh)
    scalar = NamedSharding(mesh, P())
    fn = jax.jit(
        lambda k, p, s: draw(k, p, s, (8, 64), jnp.float32(0.4)),
        in_shardings=(scalar, NamedSharding(mesh, P("data")), scalar),
        out_shardings=(scalar, scalar),
    )
    idx, w = fn(
        jax.device_put(jax.random.PRNGKey(7), scalar),
        per.priorities,
        per.size,
    )
    idx = np.asarray(jax.device_get(idx))
    w = np.asarray(jax.device_get(w))
    assert idx.min() >= 0 and idx.max() < 192
    # Row 37 holds ~98% of the mass; stratified draws must overwhelmingly
    # pick it.
    assert (idx == 37).mean() > 0.9, (idx == 37).mean()
    assert np.isfinite(w).all() and w.max() == 1.0


def test_per_sharded_chunk_updates_priorities():
    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=B,
        seed=0, fused_chunk="off", prioritized=True,
    )
    lrn = ShardedLearner(cfg, OBS, ACT, action_scale=1.0, chunk_size=3,
                         replay_sharding="sharded")
    rep = DevicePrioritizedReplay(
        1024, OBS, ACT, mesh=lrn.mesh, block_size=256,
        replay_sharding="sharded",
    )
    rep.add_packed(_rows(np.random.default_rng(5), 512))
    out = lrn.run_sample_chunk_per(rep, beta=0.5)
    assert np.isfinite(np.asarray(out.td_errors)).all()
    st = rep.state_dict()
    pr = st["priorities"]
    assert np.isfinite(pr).all() and (pr > 0).all()
    # Sampled rows re-stamped at (|td|+eps)^alpha — off the 1.0 max stamp.
    assert (np.abs(pr - 1.0) > 1e-9).any()
    assert float(st["max_priority"]) >= 1.0


# --------------------------------------------------------------------------
# device-actor insert legality (config + runtime)
# --------------------------------------------------------------------------


def test_insert_device_rows_parity_and_alignment():
    mesh = make_mesh(-1, 1)
    reps = _pair(DeviceReplay, mesh)
    dev_rows = np.random.default_rng(6).standard_normal((32, W)).astype(
        np.float32
    )
    blk = jax.device_put(
        jnp.asarray(dev_rows), NamedSharding(mesh, P(None, None))
    )
    for rep in reps.values():
        rep.insert_device_rows(blk)
    np.testing.assert_array_equal(
        reps["replicated"].state_dict()["packed"],
        reps["sharded"].state_dict()["packed"],
    )
    # Non-divisible inserts break the ptr-alignment invariant: refused.
    bad = jax.device_put(
        jnp.asarray(dev_rows[:30]), NamedSharding(mesh, P(None, None))
    )
    with pytest.raises(ValueError, match="divide over"):
        reps["sharded"].insert_device_rows(bad)


# --------------------------------------------------------------------------
# config validation
# --------------------------------------------------------------------------


def test_config_validates_sharded_mode():
    assert DDPGConfig(replay_sharding="sharded")  # legal default combo
    with pytest.raises(ValueError, match="replay_sharding"):
        DDPGConfig(replay_sharding="partitioned")
    with pytest.raises(ValueError, match="host_replay"):
        DDPGConfig(replay_sharding="sharded", host_replay=True)
    with pytest.raises(ValueError, match="scan path"):
        DDPGConfig(replay_sharding="sharded", fused_chunk="on")
    # PR 15 (docs/MESH.md): sharded replay COMPOSES with tensor
    # parallelism — ring on 'data' x params on 'model'; the old
    # model_axis rejection is lifted (parity pinned in
    # tests/test_partition.py).
    assert DDPGConfig(replay_sharding="sharded", model_axis=2)
    with pytest.raises(ValueError, match="backend"):
        DDPGConfig(replay_sharding="sharded", backend="native")
    with pytest.raises(ValueError, match="divide evenly"):
        DDPGConfig(replay_sharding="sharded", data_axis=3,
                   replay_capacity=1_000_000)
    # Device actors: chunk rows must split over the shards.
    with pytest.raises(ValueError, match="insert_device_rows"):
        DDPGConfig(
            replay_sharding="sharded", data_axis=8, replay_capacity=65536,
            actor_backend="device", num_actors=0,
            device_actor_envs=3, device_actor_chunk=1,
        )
    assert DDPGConfig(
        replay_sharding="sharded", data_axis=8, replay_capacity=65536,
        actor_backend="device", num_actors=0,
        device_actor_envs=16, device_actor_chunk=4,
    )


def test_replay_validates_alignment_at_construction():
    mesh = make_mesh(-1, 1)
    with pytest.raises(ValueError, match="capacity"):
        DeviceReplay(255, OBS, ACT, mesh=mesh, block_size=64,
                     replay_sharding="sharded")
    with pytest.raises(ValueError, match="block_size"):
        DeviceReplay(256, OBS, ACT, mesh=mesh, block_size=62,
                     replay_sharding="sharded")
    with pytest.raises(ValueError, match="mesh"):
        DeviceReplay(256, OBS, ACT, mesh=None, block_size=64,
                     replay_sharding="sharded")


# --------------------------------------------------------------------------
# background-beat deadline (ISSUE-10 satellite: no 10-minute silent stall)
# --------------------------------------------------------------------------


def test_beat_result_timeout_derives_from_pod_deadline():
    assert multihost.beat_result_timeout_s() == 600.0  # unarmed default
    multihost.configure_pod(20.0)
    try:
        t = multihost.beat_result_timeout_s()
        # 2x deadline + dispatch slack; far under the old hardcoded 600.
        assert 40.0 <= t <= 120.0, t
        multihost.grant(50.0)
        assert multihost.beat_result_timeout_s() > t  # grant extends
    finally:
        multihost.configure_pod(0.0)
    assert multihost.beat_result_timeout_s(default_s=7.0) == 7.0


# Re-tiered to slow (ISSUE 15 tier-1 budget): 30s deadline-expiry wait; the sharded train smoke + parity oracle
# keep replay-sharding tier-1 coverage
@pytest.mark.slow
def test_wedged_background_beat_surfaces_as_pod_peer_lost(monkeypatch):
    """A sync_ship whose background beat never resolves must raise typed
    PodPeerLost at the derived deadline — the exit-76 clean-abort path —
    instead of stalling for the old hardcoded 600s."""
    from distributed_ddpg_tpu.transfer.scheduler import TransferTicket

    mesh = make_mesh(-1, 1)
    rep = DeviceReplay(256, OBS, ACT, mesh=mesh, block_size=64)
    # Simulate the multi-host background-beat configuration without a
    # cluster: >1 processes (skips the single-process fast path), bg_sync
    # armed, and the issued beat never completes.
    rep._procs = 2
    rep._bg_sync = True
    monkeypatch.setattr(
        rep, "sync_ship_begin",
        lambda force=False: TransferTicket("wedged_beat"),
    )
    multihost.configure_pod(0.2)
    try:
        with pytest.raises(multihost.PodPeerLost, match="sync_ship beat"):
            rep.sync_ship()
    finally:
        multihost.configure_pod(0.0)


# --------------------------------------------------------------------------
# transfer scheduler: the shard_exchange ordered item type
# --------------------------------------------------------------------------


def test_shard_exchange_shares_ordered_lane_fifo():
    """shard_exchange items and lockstep items must execute in ONE strict
    FIFO (both are global device programs — reordering them across
    processes forks the pod), while being accounted as separate classes."""
    from distributed_ddpg_tpu.transfer import TransferScheduler

    s = TransferScheduler().start()
    try:
        order = []
        gate = threading.Event()
        t0 = s.submit("lockstep", lambda: gate.wait(10) and order.append(0))
        t1 = s.submit("shard_exchange", lambda: order.append(1))
        t2 = s.submit("lockstep", lambda: order.append(2))
        t3 = s.submit("shard_exchange", lambda: order.append(3))
        gate.set()
        for t in (t0, t1, t2, t3):
            t.result(timeout=10)
        assert order == [0, 1, 2, 3]
        snap = s.snapshot()
        assert snap["transfer_shard_exchange_items"] == 2
        assert snap["transfer_lockstep_items"] == 2
    finally:
        s.close()


def test_shard_exchange_beats_get_the_lane_deadline():
    from distributed_ddpg_tpu.transfer import TransferScheduler

    s = TransferScheduler(lockstep_timeout_s=0.3).start()
    try:
        ticket = s.submit(
            "shard_exchange", lambda: __import__("time").sleep(10),
            label="beat_1",
        )
        with pytest.raises(multihost.PodPeerLost):
            ticket.result(timeout=10)
        assert s.alive
    finally:
        s.close()


def test_sharded_beats_submit_as_shard_exchange():
    """sync_ship_begin routes sharded beats to the shard_exchange class
    (replicated beats stay lockstep) — pinned via a recording stub."""
    mesh = make_mesh(-1, 1)
    calls = []

    class FakeSched:
        def submit(self, cls, fn, nbytes=0, label=""):
            calls.append(cls)
            from distributed_ddpg_tpu.transfer.scheduler import TransferTicket

            t = TransferTicket(label)
            t._finish(result=0)
            return t

    for mode, expected in (("replicated", "lockstep"),
                           ("sharded", "shard_exchange")):
        rep = DeviceReplay(256, OBS, ACT, mesh=mesh, block_size=64,
                           replay_sharding=mode)
        rep._bg_sync = True
        rep._sched = FakeSched()
        rep.sync_ship_begin()
        assert calls[-1] == expected, (mode, calls)


# --------------------------------------------------------------------------
# tools.runs rendering
# --------------------------------------------------------------------------


def test_tools_runs_replay_sharding_digest(tmp_path):
    import json

    from distributed_ddpg_tpu.tools.runs import (
        compare_runs,
        render_summary,
        summarize_run,
    )

    recs = [
        {"kind": "train", "step": 100, "replay_ingest_bytes_per_row": 172.0,
         "replay_shard_count": 8, "replay_shard_fill_min": 100,
         "replay_shard_fill_max": 101, "replay_exchange_ms_p95": 2.0,
         "replay_device_storage_bytes": 1409024},
        {"kind": "final", "step": 200, "replay_ingest_bytes_per_row": 172.0,
         "replay_shard_count": 8, "replay_shard_fill_min": 200,
         "replay_shard_fill_max": 200, "replay_exchange_ms_p95": 1.5,
         "replay_device_storage_bytes": 1409024},
    ]
    path = tmp_path / "run.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    digest = summarize_run(str(path))
    shard = digest["replay_sharding"]
    assert shard["replay_ingest_bytes_per_row"]["last"] == 172.0
    assert shard["replay_shard_count"]["last"] == 8
    text = render_summary(digest)
    assert "replay placement" in text
    assert "replay_ingest_bytes_per_row" in text
    _, rows = compare_runs(str(path), str(path))
    assert any(r[0] == "replay_ingest_bytes_per_row" for r in rows)


# --------------------------------------------------------------------------
# reward_sample (auto-support input) reads logical rows in sharded mode
# --------------------------------------------------------------------------


def test_reward_sample_parity_across_placements():
    mesh = make_mesh(-1, 1)
    reps = _pair(DeviceReplay, mesh, capacity=512, block=64)
    data = _rows(np.random.default_rng(8), 256)
    for rep in reps.values():
        rep.add_packed(data.copy())
    ra, da = reps["replicated"].reward_sample()
    rb, db = reps["sharded"].reward_sample()
    np.testing.assert_array_equal(ra, rb)
    np.testing.assert_array_equal(da, db)
    # Strided path too (max_n < size).
    ra, _ = reps["replicated"].reward_sample(max_n=100)
    rb, _ = reps["sharded"].reward_sample(max_n=100)
    np.testing.assert_array_equal(ra, rb)


# --------------------------------------------------------------------------
# end-to-end: the trainer runs sharded and resumes from its checkpoint
# --------------------------------------------------------------------------


def test_train_smoke_sharded_replay(tmp_path):
    """Tier-1 acceptance: a sharded-replay run trains end to end and its
    records carry the replay_* placement family with the full shard
    count. (Checkpoint-format roundtrips across placements are pinned at
    unit scale by test_per_stamp_parity_and_checkpoint_roundtrip; a
    second full train run here would only re-pay the XLA compiles.)"""
    import json

    from distributed_ddpg_tpu.train import train_jax

    ckpt = str(tmp_path / "ckpt")
    cfg = DDPGConfig(
        backend="jax_tpu",
        env_id="Pendulum-v1",
        actor_hidden=(16, 16),
        critic_hidden=(16, 16),
        batch_size=16,
        num_actors=1,
        replay_sharding="sharded",
        total_env_steps=900,
        replay_min_size=128,
        replay_capacity=8192,
        eval_every=100_000,
        checkpoint_dir=ckpt,
        checkpoint_every=8,
        log_path=str(tmp_path / "a.jsonl"),
    )
    out = train_jax(cfg)
    assert out["learner_steps"] > 0
    assert np.isfinite(out["final_return"])
    recs = [json.loads(l) for l in open(cfg.log_path)]
    shardy = [r for r in recs if "replay_shard_count" in r]
    assert shardy and shardy[-1]["replay_shard_count"] == 8
    assert any(r.get("replay_ingest_bytes", 0) > 0 for r in shardy)
    # The run checkpointed in the logical wire format (resumable by
    # either placement — unit-pinned above).
    from distributed_ddpg_tpu import checkpoint as ckpt_lib

    assert ckpt_lib.latest_step(ckpt) is not None


def test_sharded_per_draw_clamps_to_live_rows():
    """Partially-filled buffer: every drawn index must stay < size even
    when a stratified uniform lands on a shard-interval boundary — the
    sharded twin of draw_per_indices' size clamp (an unclamped draw
    would select an empty zero-priority slot and its (size*1e-12)^-beta
    IS weight would crush the batch's normalization)."""
    mesh = make_mesh(-1, 1)
    per = DevicePrioritizedReplay(
        256, OBS, ACT, mesh=mesh, block_size=64, replay_sharding="sharded"
    )
    per.add_packed(_rows(np.random.default_rng(9), 64))
    # Awkward live size (not a shard multiple) with uneven mass.
    st = per.state_dict()
    st["packed"] = st["packed"][:57]
    st["size"] = np.asarray(57)
    st["ptr"] = np.asarray(0)
    st["priorities"] = np.linspace(0.1, 5.0, 57).astype(np.float32)
    per.load_state_dict(st)
    draw = make_sharded_per_draw(mesh)
    scalar = NamedSharding(mesh, P())
    fn = jax.jit(
        lambda k, p, s: draw(k, p, s, (8, 64), jnp.float32(0.4)),
        in_shardings=(scalar, NamedSharding(mesh, P("data")), scalar),
        out_shardings=(scalar, scalar),
    )
    for seed in range(6):
        idx, w = fn(
            jax.device_put(jax.random.PRNGKey(seed), scalar),
            per.priorities, per.size,
        )
        idx = np.asarray(jax.device_get(idx))
        w = np.asarray(jax.device_get(w))
        assert idx.min() >= 0 and idx.max() < 57, (seed, idx.max())
        assert np.isfinite(w).all() and w.max() == 1.0
        # No zero-priority slot was ever selected: weights stay in a sane
        # dynamic range (an empty slot would produce a ~1e5x outlier max
        # that normalizes everything else to ~0).
        assert w.min() > 1e-4, (seed, w.min())


# --------------------------------------------------------------------------
# all-writer slices + N->M reshard matrix (ISSUE 17; docs/REPLAY_SHARDING.md
# 'All-writer replay slices', docs/RESILIENCE.md shrink/grow state machine)
# --------------------------------------------------------------------------


def test_slice_state_dict_single_process_covers_ring():
    """Single-process slice_state_dict is the whole logical ring as one
    1-of-1 slice: positions [0, size), rows in wire order, and (PER) the
    live priority vector — so a 1-process 'pod' writes the same format N
    writers do and merge_slice_states([slice]) is the identity."""
    from distributed_ddpg_tpu.replay.device import merge_slice_states

    mesh = make_mesh(-1, 1)
    for cls in (DeviceReplay, DevicePrioritizedReplay):
        rep = cls(256, OBS, ACT, mesh=mesh, block_size=64,
                  replay_sharding="sharded")
        rep.add_packed(_rows(np.random.default_rng(5), 128))
        sl = rep.slice_state_dict()
        np.testing.assert_array_equal(
            np.asarray(sl["positions"]), np.arange(128, dtype=np.int64)
        )
        assert int(sl["capacity"]) == 256
        st = rep.state_dict()
        merged = merge_slice_states([sl])
        np.testing.assert_array_equal(merged["packed"], st["packed"])
        assert int(merged["ptr"]) == int(st["ptr"])
        assert int(merged["size"]) == int(st["size"])
        if "priorities" in st:
            np.testing.assert_array_equal(
                np.asarray(merged["priorities"], np.float32),
                np.asarray(st["priorities"], np.float32),
            )


def test_reshard_matrix_roundtrip_equals_single_host_oracle(tmp_path):
    """The N->M reshard acceptance matrix over {1,2,4}^2, uniform + PER:
    an n-writer slice set (the split of a single-host oracle state)
    written through checkpoint.write_replay_slice, digest-verified,
    loaded back, merged, and loaded into a sharded buffer must reproduce
    the oracle's logical ring bit-for-bit — including the PER priority
    vector rebuild — and re-splitting to m writers round-trips the same
    state (the grow/shrink algebra is position-driven, so the writer
    count is free to change at every restart)."""
    from distributed_ddpg_tpu import checkpoint as ckpt_lib
    from distributed_ddpg_tpu.replay.device import (
        merge_slice_states,
        split_slice_state,
    )

    mesh = make_mesh(-1, 1)
    for cls in (DeviceReplay, DevicePrioritizedReplay):
        rng = np.random.default_rng(11)
        oracle_rep = cls(256, OBS, ACT, mesh=mesh, block_size=64,
                         replay_sharding="replicated")
        oracle_rep.add_packed(_rows(rng, 192))
        oracle = oracle_rep.state_dict()
        if "priorities" in oracle:
            # Non-uniform priorities so the vector rebuild is observable
            # (a uniform stamp would mask a dropped/reordered slice).
            oracle["priorities"] = np.linspace(
                0.2, 4.0, int(oracle["size"])
            ).astype(np.float32)
            oracle["max_priority"] = np.asarray(5.0, np.float32)
        target = cls(256, OBS, ACT, mesh=mesh, block_size=64,
                     replay_sharding="sharded")
        for n in (1, 2, 4):
            d = str(tmp_path / f"{cls.__name__}_n{n}")
            for k, sl in enumerate(split_slice_state(oracle, n, 256)):
                ckpt_lib.write_replay_slice(d, 7, k, n, sl)
            complete, nprocs = ckpt_lib.verify_replay_slices(d, 7)
            assert complete and nprocs == n, (complete, nprocs)
            merged = merge_slice_states(ckpt_lib.load_replay_slices(d, 7))
            np.testing.assert_array_equal(merged["packed"], oracle["packed"])
            # The production load path: the merged wire state lands in a
            # sharded buffer (the M-process counterpart scatters the same
            # replicated logical rows through the reshard program).
            target.load_state_dict(merged)
            back = target.state_dict()
            np.testing.assert_array_equal(back["packed"], oracle["packed"])
            assert int(back["ptr"]) == int(oracle["ptr"])
            assert int(back["size"]) == int(oracle["size"])
            if "priorities" in oracle:
                np.testing.assert_array_equal(
                    np.asarray(back["priorities"], np.float32),
                    oracle["priorities"],
                )
                assert float(back["max_priority"]) == 5.0
            for m in (1, 2, 4):
                # Re-split to m writers (the next incarnation's slice
                # set) and merge back: bit-identical to the oracle.
                reslices = split_slice_state(back, m, 256)
                assert len(reslices) == m
                assert sum(
                    len(s["positions"]) for s in reslices
                ) == int(oracle["size"])
                remerged = merge_slice_states(reslices)
                np.testing.assert_array_equal(
                    remerged["packed"], oracle["packed"]
                )
                if "priorities" in oracle:
                    np.testing.assert_array_equal(
                        np.asarray(remerged["priorities"], np.float32),
                        oracle["priorities"],
                    )


def test_merge_slice_states_rejects_holes_overlaps_and_forks():
    """A slice set that mixes worlds must fail LOUDLY: silently loading a
    holed or overlapping set would corrupt the data distribution the
    learner resumes on (docs/REPLAY_SHARDING.md)."""
    from distributed_ddpg_tpu.replay.device import (
        ReplayUsageError,
        merge_slice_states,
        split_slice_state,
    )

    rng = np.random.default_rng(13)
    state = {
        "packed": rng.standard_normal((64, W)).astype(np.float32),
        "ptr": np.asarray(0), "size": np.asarray(64),
    }
    a, b = split_slice_state(state, 2, 256)
    with pytest.raises(ReplayUsageError, match="does not cover"):
        merge_slice_states([a])                       # hole
    with pytest.raises(ReplayUsageError, match="overlap"):
        merge_slice_states([a, a])                    # overlap
    forked = dict(b, ptr=np.asarray(32))
    with pytest.raises(ReplayUsageError, match="ring scalars"):
        merge_slice_states([a, forked])               # mixed steps
    with pytest.raises(ReplayUsageError, match="empty"):
        merge_slice_states([])


def test_single_shard_sharded_load_state_dict_roundtrip():
    """A 1-device 'sharded' ring (data axis 1 — what a plain CLI run on
    one CPU device builds) must still load checkpoints: device_get hands
    back a read-only buffer and the logical permutation is an identity
    there, so the load path must copy before writing (regression: the
    elastic CLI resume crashed with 'assignment destination is
    read-only')."""
    from jax.sharding import Mesh

    mesh = Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model")
    )
    rng = np.random.default_rng(17)
    for cls in (DeviceReplay, DevicePrioritizedReplay):
        src = cls(128, OBS, ACT, mesh=mesh, block_size=32,
                  replay_sharding="sharded")
        src.add_packed(_rows(rng, 96))
        state = src.state_dict()
        dst = cls(128, OBS, ACT, mesh=mesh, block_size=32,
                  replay_sharding="sharded")
        dst.load_state_dict(state)
        back = dst.state_dict()
        np.testing.assert_array_equal(back["packed"], state["packed"])
        assert int(back["size"]) == 96
        if "priorities" in state:
            np.testing.assert_array_equal(
                np.asarray(back["priorities"], np.float32),
                np.asarray(state["priorities"], np.float32),
            )
