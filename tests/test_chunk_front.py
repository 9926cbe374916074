"""The scan leg's front (ops/chunk_front.py): the one-pass kernel in
interpret mode against `unpack_batch(storage[idx])`, field for field and bit
for bit; the rule that says which launches take it; and the learner's wiring
of it, on one device and on a data mesh. What the chip's compiler makes of it
(one reader of the gathered block, no relayout copy, Mosaic's default VMEM):
tests/test_ring_layout.py; on the chip itself: chip_smoke.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.ops import chunk_front
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.replay.device import DeviceReplay, ring_layout
from distributed_ddpg_tpu.types import packed_width, unpack_batch

ROUNDED_FIELDS = ("obs", "action", "next_obs")
CAPACITY = 1000


def bits(x):
    return np.asarray(x).view(np.uint32)


def ring_and_indices(obs_dim, act_dim, K, B, seed=0):
    """A ring of CAPACITY seeded rows (signed zeros, subnormals and values
    that round up and down to bfloat16 among them) and [K, B] indices that
    hold row 0, the last row and duplicates inside one block."""
    rng = np.random.default_rng(seed)
    rows = rng.standard_normal((CAPACITY, packed_width(obs_dim, act_dim))).astype(np.float32)
    rows[0, :6] = [0.0, -0.0, 1e-40, 1.00390625, 1.01171875, -3.0e38]
    idx = rng.integers(0, CAPACITY, (K, B)).astype(np.int32)
    idx[0, :5] = [0, CAPACITY - 1, 7, 7, 0]
    idx[-1, -3:] = [CAPACITY - 1, 7, 7]
    return jnp.asarray(rows), jnp.asarray(idx)


@pytest.mark.parametrize("rounded", [True, False])
@pytest.mark.parametrize("batch", [256, 384])  # one block an update, and three
@pytest.mark.parametrize(
    "obs_dim,act_dim",
    [
        (376, 17),  # Humanoid: 772 floats, seven lane groups, the last 4 wide
        (108, 21),  # PQL's Humanoid: 240, the action across the two groups
        (50, 5),    # 108: one group, partial
        (61, 3),    # 128: one group, whole
    ],
)
def test_every_field_is_unpack_batchs_bit_for_bit(obs_dim, act_dim, batch, rounded):
    storage, idx = ring_and_indices(obs_dim, act_dim, K=3, B=batch)
    want = unpack_batch(storage[idx], obs_dim, act_dim)
    got = chunk_front.cut_rows(storage[idx], obs_dim, act_dim, rounded)
    for field in want._fields:
        w, g = getattr(want, field), getattr(got, field)
        if rounded and field in ROUNDED_FIELDS:
            # to nearest even, as XLA's own convert rounds
            w = w.astype(jnp.bfloat16).astype(jnp.float32)
        assert g.shape == w.shape and g.dtype == w.dtype == jnp.float32, field
        assert np.array_equal(bits(g), bits(w)), field


@pytest.mark.parametrize(
    "batch,width,rows",
    [(256, 772, 256), (8192, 240, 1024), (1024, 772, 256), (384, 772, 128), (64, 772, 0), (100, 240, 0)],
)
def test_a_block_is_lanes_of_the_batch_inside_a_mebibyte(batch, width, rows):
    assert chunk_front.block_rows(batch, width) == rows


HUMANOID = dict(width=772, batch=256, layout="row_major", replay_sharded=False, model_axis=1, native=True)


@pytest.mark.parametrize(
    "seen,front",
    [
        ({}, "cut"),
        (dict(width=240, batch=8192), "cut"),  # PQL
        (dict(width=43, layout="packed"), "xla"),  # HalfCheetah: the megakernel's ring
        (dict(width=65, layout="compact"), "xla"),  # Ant
        (dict(layout="compact"), "xla"),  # the same width off the TPU's Format
        (dict(replay_sharded=True), "xla"),
        (dict(model_axis=2), "xla"),
        (dict(native=False), "xla"),
        (dict(batch=64), "xla"),
    ],
)
def test_the_rule_reads_what_the_program_sees(seen, front):
    assert chunk_front.front_for(**{**HUMANOID, **seen}) == front


def test_the_rule_s_layouts_are_the_rings_own():
    assert ring_layout(772) == ring_layout(240) == "row_major"
    assert ring_layout(43) == "packed" and ring_layout(65) == "compact"


def test_rounding_only_where_every_reader_is_a_one_pass_matmul():
    sac = DDPGConfig(sac=True)
    assert chunk_front.rounds_inputs(sac) and chunk_front.rounds_inputs(DDPGConfig())
    # batch statistics read the observations in float32
    assert not chunk_front.rounds_inputs(DDPGConfig(sac=True, crossq=True, policy_delay=3))
    with jax.default_matmul_precision("highest"):
        assert not chunk_front.rounds_inputs(sac)


OBS, ACT = 50, 5  # 108 floats a row: row-major by the width rule


def learner_and_ring(as_on_a_tpu, native, devices=1, model_axis=1, **config):
    """A scan-leg learner built as on a real TPU (`native`) or as here, and a
    ring of rows that bfloat16 holds exactly, so that rounding is the
    identity and both fronts must give the same numbers on the CPU."""
    as_on_a_tpu(native)
    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 32), batch_size=128, seed=3,
        fused_chunk="off", **{"sac": True, **config},
    )
    mesh = mesh_lib.make_mesh(devices // model_axis, model_axis, devices=jax.devices()[:devices])
    learner = ShardedLearner(cfg, OBS, ACT, action_scale=1.0, mesh=mesh, chunk_size=3)
    replay = DeviceReplay(capacity=2048, obs_dim=OBS, act_dim=ACT, mesh=mesh, block_size=512)
    rows = np.random.default_rng(0).standard_normal((1024, packed_width(OBS, ACT))).astype(np.float32)
    replay.add_packed(np.asarray(jnp.asarray(rows).astype(jnp.bfloat16).astype(jnp.float32)))
    replay.flush()
    return learner, replay


@pytest.mark.parametrize("devices", [1, 4])
def test_the_learner_s_chunk_under_both_fronts_ends_in_one_state(as_on_a_tpu, devices):
    plain, ring_a = learner_and_ring(as_on_a_tpu, native=False, devices=devices)
    fronted, ring_b = learner_and_ring(as_on_a_tpu, native=True, devices=devices)
    assert (plain.chunk_front, fronted.chunk_front) == ("xla", "cut")
    for _ in range(2):
        a = jax.block_until_ready(plain.run_sample_chunk(ring_a))
        b = jax.block_until_ready(fronted.run_sample_chunk(ring_b))
        assert np.array_equal(bits(a.td_errors), bits(b.td_errors))
    for x, y in zip(jax.tree.leaves(plain.state), jax.tree.leaves(fronted.state)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    # the kernel's instructions (interpreted here) read under `cut`; nothing
    # the front adds is left without a scope
    table = fronted.chunk_ops()
    assert "cut" in table["ops"].values()
    assert len(table["ops"]) > len(plain.chunk_ops()["ops"])


@pytest.mark.parametrize(
    "how",
    [
        dict(config=dict(guardrails=True)),  # the guarded chunk screens the gathered rows
        dict(config=dict(replay_sharding="sharded"), devices=2),
        dict(devices=2, model_axis=2),
        dict(obs=17, act=6),  # packed lines
        dict(obs=25, act=12),  # 65 floats: compact
        dict(config=dict(batch_size=64)),
    ],
)
def test_the_learner_keeps_unpack_batch_where_the_rule_says_no(as_on_a_tpu, how):
    as_on_a_tpu()
    config = dict(how.get("config", {}))
    sharding = config.pop("replay_sharding", "replicated")
    cfg = DDPGConfig(
        actor_hidden=(32, 32), critic_hidden=(32, 32), seed=3, fused_chunk="off",
        **{"batch_size": 128, "sac": True, **config},
    )
    devices, model_axis = how.get("devices", 1), how.get("model_axis", 1)
    mesh = mesh_lib.make_mesh(devices // model_axis, model_axis, devices=jax.devices()[:devices])
    learner = ShardedLearner(
        cfg, how.get("obs", OBS), how.get("act", ACT), action_scale=1.0, mesh=mesh,
        chunk_size=3, replay_sharding=sharding,
    )
    assert learner.chunk_front == "xla"
