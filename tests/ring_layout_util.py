"""Shared checks of the replay ring's device layout, used by BOTH tiers:

- tests/test_ring_layout.py compiles the programs at Humanoid width and a
  small capacity (on the CPU a guard on the plumbing; for a described v5e,
  the guard on the layout itself), and tests/test_packed_ring.py fills
  small rings of every kind past a wrap and reads each row back;
- chip_smoke.py runs the same bodies on the chip: the Humanoid ring at the
  benchmark's 1.4e6 rows, and a HalfCheetah-wide packed ring of the papers'
  1e6 rows filled through the ingest path.

A ring-sized `copy` or `transpose` in a program that takes the ring is XLA
re-laying the whole ring before it gathers from or scatters into it: one
pass over gigabytes per launch (replay/device.py ring_format; PERF.md PR 26).
"""

import re

# SAC at Humanoid-v4 shapes: outside fits_vmem, so the learner picks the
# scan chunk by itself (the benchmark's sac-humanoid configuration).
HUMANOID_OBS, HUMANOID_ACT, HUMANOID_SCALE = 376, 17, 0.4
SAC_HUMANOID_FLAGS = [
    "--backend=jax_tpu",
    "--env_id=Humanoid-v4",
    "--sac=true",
    "--batch_size=256",
    "--actor_lr=3e-4",
    "--critic_lr=3e-4",
    "--tau=0.005",
]


def ring_sized_copies(hlo_text: str, shape) -> list:
    """The `copy` and `transpose` instructions of an optimised HLO module
    whose result has the ring's shape, as 'name = f32[..]{layout} op'."""
    rows, width = shape
    pat = re.compile(
        r"\s*(?:ROOT )?%?([\w.\-]+) = (f32\[" + f"{rows},{width}"
        + r"\]\{[^}]*\}) (copy|transpose)\("
    )
    return [
        f"{m.group(1)} = {m.group(2)} {m.group(3)}"
        for m in map(pat.match, hlo_text.splitlines())
        if m
    ]


def humanoid_ring_programs(capacity: int, chunk: int):
    """Build the SAC/Humanoid scan learner and its ring, and compile the two
    programs that take the ring: `jit_ring_insert` and the scan
    `sample_chunk_fn`. Returns (replay, {program: ring-sized copies})."""
    import jax
    import numpy as np

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner
    from distributed_ddpg_tpu.replay.device import DeviceReplay

    cfg = DDPGConfig.from_flags(
        SAC_HUMANOID_FLAGS + [f"--replay_capacity={capacity}"]
    )
    learner = ShardedLearner(
        cfg, HUMANOID_OBS, HUMANOID_ACT, HUMANOID_SCALE, 0.0, chunk_size=chunk
    )
    assert not learner.fused_chunk_active, "Humanoid SAC must ride the scan leg"
    replay = DeviceReplay(
        capacity, HUMANOID_OBS, HUMANOID_ACT, mesh=learner.mesh, block_size=1024
    )
    shape = replay.storage.shape
    block = jax.device_put(
        np.zeros((replay.block_size, replay.width), np.float32),
        jax.sharding.NamedSharding(learner.mesh, jax.sharding.PartitionSpec()),
    )
    programs = {
        "jit_ring_insert": replay._insert.lower(
            replay.storage, block, replay.ptr, replay.size
        ),
        "jit_sample_chunk_fn": learner._sample_chunk_step.lower(
            learner.state, learner._key, *replay.device_state()
        ),
    }
    return replay, {
        name: ring_sized_copies(lowered.compile().as_text(), shape)
        for name, lowered in programs.items()
    }


def fill_past_a_wrap(replay, n_rows: int, push: int, seed: int = 0):
    """Stage `n_rows` seeded rows (a multiple of the block, more than the
    capacity) through the ingest path in pushes of `push` rows, so that
    super-blocks of several sizes ship, and drain. Returns the numpy ring
    the rows make: what every read of `replay` must give back."""
    import numpy as np

    rows = np.random.default_rng(seed).standard_normal(
        (n_rows, replay.width)
    ).astype(np.float32)
    want = np.zeros((replay.capacity, replay.width), np.float32)
    for at in range(0, n_rows, push):
        part = rows[at : at + push]
        replay.add_packed(part)
        want[(at + np.arange(len(part))) % replay.capacity] = part
    replay.drain_pending()
    assert int(replay.ptr) == n_rows % replay.capacity
    assert len(replay) == min(n_rows, replay.capacity)
    return want


def assert_reads_back(replay, want, n_idx: int = 200) -> None:
    """Every row of `replay` is `want`'s, bit for bit, through
    `device_state()[0][idx]` outside a jit and inside one that takes the
    ring as an argument (what benchmarks/harness/check.py does), through
    int and slice keys, and through the whole-ring host view."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    storage, _ = replay.device_state()
    cap = replay.capacity
    assert storage.shape == want.shape
    idx = np.random.default_rng(1).integers(0, cap, (8, n_idx // 8))
    idx[0, :2] = (0, cap - 1)
    np.testing.assert_array_equal(np.asarray(storage[idx]), want[idx])
    np.testing.assert_array_equal(
        np.asarray(storage[jnp.asarray(idx[0])]), want[idx[0]]
    )
    inside = jax.jit(lambda s, i: s[i])(storage, idx)
    np.testing.assert_array_equal(np.asarray(inside), want[idx])
    np.testing.assert_array_equal(np.asarray(storage[7]), want[7])
    np.testing.assert_array_equal(
        np.asarray(storage[cap - 10 :, 1:3]), want[cap - 10 :, 1:3]
    )
    np.testing.assert_array_equal(np.asarray(jax.device_get(storage)), want)
