"""Narrow replay rows packed G = 128 // width to a 128-lane line
(replay/device.py PackedRing; docs/INGEST.md "The ring's device layout").

The index stream, the rows and their order are what they were, so every
row a program reads through `device_state()[0][idx]` is bit for bit the
row a plain numpy ring holds, and every chunk program gives on a packed
ring what it gives on the same rows in a plain [rows, width] array. The
chip's `correct` cannot show a wrong slot: the benchmark's reference reads
its rows through the ring's own read (PERF.md §7), so this file carries it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.parallel.mesh import make_mesh
from distributed_ddpg_tpu.replay.device import (
    DevicePrioritizedReplay,
    DeviceReplay,
    PackedRing,
    merge_slice_states,
    split_slice_state,
)
from ring_layout_util import assert_reads_back, fill_past_a_wrap

# width = 2 * obs + act + 3: 10 (Pendulum), 43 (HalfCheetah) and 64 are
# packed 12, 2 and 2 to a line; 65 (Ant) and 772 (Humanoid) are not.
SHAPES = {10: (3, 1), 43: (17, 6), 64: (30, 1), 65: (30, 2), 772: (376, 17)}
WIDTHS = sorted(SHAPES)
CAP, BLOCK = 100, 8  # 100 divides by neither 12 nor 8: the last line is part full


def _ring(width, **kw):
    kw.setdefault("max_coalesce", 4)
    return DeviceReplay(CAP, *SHAPES[width], block_size=BLOCK, **kw)


def _rows(n, width, seed=0):
    return np.random.default_rng(seed).standard_normal((n, width)).astype(np.float32)


def _read_everywhere(replay, want):
    storage, _ = replay.device_state()
    assert isinstance(storage, PackedRing) == (replay.width <= 64)
    assert_reads_back(replay, want)


def _filled_past_a_wrap(width, replay=None):
    """Staged in pushes of three blocks so super-blocks of 1, 2 and 4 blocks
    ship, past one wrap and a half."""
    replay = _ring(width) if replay is None else replay  # an empty ring is falsy
    return replay, fill_past_a_wrap(replay, 20 * BLOCK, 3 * BLOCK)


@pytest.mark.parametrize("width", WIDTHS)
def test_rule_and_counters(width):
    replay = _ring(width)
    snap = replay.ingest_snapshot()
    if width <= 64:
        per_line = 128 // width
        assert snap["replay_ring_layout"] == replay.ring_layout == "packed"
        assert snap["replay_row_bytes_device"] == 512 // per_line
        lines = -(-CAP // per_line)
        assert replay.storage.lines.shape == (lines, 128)
        assert snap["replay_device_storage_bytes"] == lines * 512
    else:
        assert snap["replay_ring_layout"] == "compact"  # row-major is the TPU's
        assert snap["replay_row_bytes_device"] == 4 * width
        assert replay.storage.shape == (CAP, width) and not isinstance(replay.storage, PackedRing)


@pytest.mark.parametrize("width", WIDTHS)
def test_fill_wrap_and_super_blocks_read_back(width):
    replay, want = _filled_past_a_wrap(width)
    assert replay.ingest_snapshot()["ingest_coalesce_mean"] > 1  # super-blocks did ship
    _read_everywhere(replay, want)


@pytest.mark.parametrize("width", WIDTHS)
def test_device_rows_of_any_count_land_unaligned(width):
    replay, want = _filled_past_a_wrap(width)
    at = int(replay.ptr)
    for m in (1, 3, 7, 13, CAP - 1):
        rows = _rows(m, width, seed=m)
        assert replay.insert_device_rows(jnp.asarray(rows)) == m
        want[(at + np.arange(m)) % CAP] = rows
        at = (at + m) % CAP
        assert int(replay.ptr) == at
    _read_everywhere(replay, want)


@pytest.mark.parametrize("width", WIDTHS)
def test_save_restore_and_a_state_in_the_parents_form(width):
    """A checkpoint holds logical rows: what the ring saves is what the
    parent's compact ring saved, and a state written that way restores."""
    replay = _ring(width)
    rows = _rows(7 * BLOCK, width)
    replay.add_packed(rows)
    replay.drain_pending()
    state = replay.state_dict()
    parents = {"packed": rows.copy(), "ptr": np.asarray(7 * BLOCK), "size": np.asarray(7 * BLOCK)}
    assert state.keys() == parents.keys()
    for k in parents:
        np.testing.assert_array_equal(state[k], parents[k])
    want = np.zeros((CAP, width), np.float32)
    want[: len(rows)] = rows
    for saved in (state, parents):
        restored = _ring(width)
        restored.load_state_dict(saved)
        assert type(restored.storage) is type(replay.storage)
        _read_everywhere(restored, want)
        # and goes on taking inserts where the saved one stopped
        more = _rows(BLOCK, width, seed=3)
        restored.add_packed(more)
        restored.drain_pending()
        after = want.copy()
        after[7 * BLOCK : 8 * BLOCK] = more
        _read_everywhere(restored, after)


@pytest.mark.parametrize("width", WIDTHS)
def test_slices_split_merge_and_restore(width):
    replay, want = _filled_past_a_wrap(width)
    one = replay.slice_state_dict()
    np.testing.assert_array_equal(one["rows"], want)
    merged = merge_slice_states(split_slice_state(replay.state_dict(), 3, CAP))
    restored = _ring(width)
    restored.load_state_dict(merged)
    assert int(restored.ptr) == int(replay.ptr)
    _read_everywhere(restored, want)


@pytest.mark.parametrize("width", [10, 43])
def test_replicated_on_a_mesh_and_per(width):
    mesh = make_mesh(-1, 1)
    replay = DevicePrioritizedReplay(CAP, *SHAPES[width], mesh=mesh, block_size=BLOCK, max_coalesce=4)
    replay, want = _filled_past_a_wrap(width, replay)
    assert isinstance(replay.storage, PackedRing)
    assert replay.storage.sharding.is_fully_replicated
    assert replay.storage.devices() == set(mesh.devices.flat)
    _read_everywhere(replay, want)
    np.testing.assert_array_equal(np.asarray(replay.priorities), np.ones(CAP, np.float32))
    rewards, discounts = replay.reward_sample()
    obs, act = SHAPES[width]
    np.testing.assert_array_equal(rewards, want[:, obs + act])
    np.testing.assert_array_equal(discounts, want[:, obs + act + 1])


def test_row_sharded_narrow_ring_stays_compact():
    replay = DeviceReplay(
        128, 3, 1, mesh=make_mesh(-1, 1), block_size=16, replay_sharding="sharded"
    )
    assert replay.ring_layout == "compact" and not isinstance(replay.storage, PackedRing)
    assert replay.ingest_snapshot()["replay_row_bytes_device"] == 40


# --- the chunk programs: a packed ring against the same rows in a plain
# [rows, width] array (what benchmarks/tests/synthetic.py hands them) ---


def _learner_and_rings(leg, per, shape):
    obs, act = shape
    cfg = DDPGConfig(
        actor_hidden=(16, 16), critic_hidden=(16, 16), batch_size=8,
        prioritized=per, fused_chunk=leg, seed=3,
    )
    mesh = make_mesh(1, 1, devices=jax.devices()[:1])
    cls = DevicePrioritizedReplay if per else DeviceReplay
    rings = []
    for _ in range(2):
        ring = cls(256, obs, act, mesh=mesh, block_size=32)
        ring.add_packed(_rows(160, ring.width, seed=5))
        ring.drain_pending()
        rings.append(ring)
    packed, plain = rings
    assert isinstance(packed.storage, PackedRing)
    plain.storage = jax.device_put(np.asarray(packed.storage), packed.storage.sharding)
    assert plain.device_state()[0].shape == packed.device_state()[0].shape
    learners = [
        ShardedLearner(cfg, obs, act, action_scale=1.0, mesh=mesh, chunk_size=3)
        for _ in rings
    ]
    assert learners[0].fused_chunk_active == (leg == "on")
    return learners, rings


@pytest.mark.parametrize("shape", [(4, 2), (17, 6)], ids=["w13", "w43"])
@pytest.mark.parametrize("per", [False, True], ids=["uniform", "per"])
@pytest.mark.parametrize("leg", ["on", "off"], ids=["kernel", "scan"])
def test_chunk_on_packed_ring_is_the_chunk_on_plain_rows(leg, per, shape):
    learners, rings = _learner_and_rings(leg, per, shape)
    got = []
    for learner, ring in zip(learners, rings):
        for _ in range(2):  # the second chunk draws with the key the first returned
            out = (
                learner.run_sample_chunk_per(ring, beta=0.5)
                if per
                else learner.run_sample_chunk(ring)
            )
        got.append(jax.device_get((out, learner._key, ring.priorities if per else None)))
    flat_packed, tree_packed = jax.tree.flatten(got[0])
    flat_plain, tree_plain = jax.tree.flatten(got[1])
    assert tree_packed == tree_plain
    for a, b in zip(flat_packed, flat_plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.isfinite(float(got[0][0].metrics["critic_loss"]))
