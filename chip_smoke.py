"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

Drives the main path once, in THIS process, through the function the CLI
calls (`distributed_ddpg_tpu.train.train(DDPGConfig.from_flags([...]))`) at
the full width of the model the repo benchmarks: DDPG, HalfCheetah-v4
(obs 17, act 6), 2x256 actor and critic, batch 64, host actor processes
feeding the HBM replay ring, every default left alone (mesh over all
visible chips, learner_chunk, fused_chunk=auto). Two legs back to back:
the default leg (Pallas megakernel; fused-mesh on several chips) and
`--fused_chunk=off` (the XLA scan chunk every other feature rides). Before
them, the kernel is checked against the scan step on one small chunk
(tests/fused_parity_util.py, the body tests/test_tpu.py runs). After them,
the replay ring at the scan leg's benchmark size (SAC, Humanoid-v4 width,
1.4e6 rows): the two programs that take it must hold no ring-sized copy
(tests/ring_layout_util.py, the body tests/test_ring_layout.py runs). And a
HalfCheetah-wide ring of the papers' 1e6 rows, packed two rows to a
128-lane line, filled through the ingest path past one wrap: every row
must read back identical to the host's copy (the body
tests/test_packed_ring.py runs), with no ring-sized copy in its programs.
Last, the scan leg's front (ops/chunk_front.py): rows read back through
the one-pass kernel at the two row-major cells' sizes (1.4e6 x 772 and
5e6 x 240) against the host's, and one launch of the SAC chunk from one
state and one ring under both fronts, whose TrainStates must be equal bit
for bit.

Exit 0 only if every check held. Stdout then ends with two JSON lines: the
facts of the run (`{"facts": {...}}`: versions, legs, compile cache, ...)
and, last, the verdict with exactly these keys, the device as JAX reports
it: `{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`.
A failed check is one line on stderr saying why, the verdict with
`"ok": false`, and exit 1. Nothing is caught and downgraded. Without a TPU
(JAX_PLATFORMS=cpu, or no chip) it prints no verdict and exits 2 in
seconds, before any training.

    python chip_smoke.py            # on the chip, from the repo root

All JAX work sits under the __main__ guard: ActorPool spawns its workers,
each re-imports this module, and a worker must never import JAX or reach
the chip (one process per chip).
"""

import json
import math
import multiprocessing as mp
import os
import sys
import time

# README quick-start flags plus sizes that make this a smoke, not a
# benchmark. No mesh, chunk, kernel or platform flag: the defaults are what
# is being proven. 60k env steps: the learner free-runs until the actors
# have delivered the budget, and with a warm compile cache the first chunk
# is done in ~1.4 s, before the ring-insert programs (each under the
# cache's 1 s floor, so compiled every run) have stopped holding the
# dispatch lock — at 30k that left 4 chunks where 3 are required (my chip
# run, PR 21). watchdog_s turns a wedged device call into stacks and exit
# 70 instead of the caller's timeout.
FLAGS = [
    "--backend=jax_tpu",
    "--env_id=HalfCheetah-v4",
    "--num_actors=4",
    "--replay_min_size=1000",
    "--replay_capacity=100000",
    "--total_env_steps=60000",
    "--eval_every=0",
    "--watchdog_s=120",
]
LEGS = (("default", []), ("scan", ["--fused_chunk=off"]))
OBS, ACT = 17, 6  # HalfCheetah-v4
INGEST_BLOCK = 1024  # train_jax's DeviceReplay block: one padded flush at most
RING_ROWS = 1_400_000  # the sac-humanoid cell's ring
PACKED_RING_ROWS = 1_000_000  # the papers' ring, at HalfCheetah's 43 floats a row
# (ring rows, obs, act, updates a launch, batch) of the cells whose launches
# take the cut front: sac-humanoid.free and pql-isaac-humanoid.devactors
FRONT_SHAPES = {"humanoid": (RING_ROWS, 376, 17, 800, 256), "pql": (5_000_000, 108, 21, 96, 8192)}


class SmokeFailure(Exception):
    pass


def check(cond, why):
    if not cond:
        raise SmokeFailure(why)


def verdict_line(ok, device):
    """The last stdout line: exactly `ok` and `device` (platform, kind,
    count) — what the chip check parses; everything else is in the facts
    line before it."""
    return json.dumps(
        {
            "ok": bool(ok),
            "device": {
                "platform": str(device["platform"]),
                "kind": str(device["kind"]),
                "count": int(device["count"]),
            },
        }
    )


def cache_entries(path):
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def run_parity():
    """Kernel vs scan step on one 8-step chunk at full width, natively
    compiled — the repo's own parity body at its on-chip tolerances."""
    from fused_parity_util import assert_fused_matches_scan

    from distributed_ddpg_tpu.config import DDPGConfig

    cfg = DDPGConfig(
        actor_hidden=(256, 256), critic_hidden=(256, 256), batch_size=64, seed=3
    )
    metrics = assert_fused_matches_scan(
        cfg, OBS, ACT, 8, 1.0, 0.0, interpret=None, rtol=2e-2, atol=1e-2
    )
    return {"critic_loss": round(float(metrics["critic_loss"]), 6)}


def run_ring_layout():
    """The Humanoid-wide ring at the benchmark's size, in the layout its
    owner picks for it: compile `jit_ring_insert` and the scan
    `sample_chunk_fn` for it and refuse a `copy` or `transpose` with the
    ring's shape in either (replay/device.py ring_format)."""
    from ring_layout_util import humanoid_ring_programs

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel.learner import resolve_learner_chunk

    chunk = resolve_learner_chunk(DDPGConfig())
    replay, copies = humanoid_ring_programs(RING_ROWS, chunk)
    for program, found in copies.items():
        check(not found, f"ring: {program} copies the whole ring: {found}")
    snap = replay.ingest_snapshot()
    check(
        snap["replay_ring_layout"] == "row_major"
        and snap["replay_row_bytes_device"] == 3584,
        f"ring: {snap['replay_ring_layout']}, {snap['replay_row_bytes_device']} B a row",
    )
    return {
        "shape": list(replay.storage.shape),
        "format": str(replay.storage.format.layout),
        "layout": snap["replay_ring_layout"],
        "row_bytes_device": snap["replay_row_bytes_device"],
        "programs": sorted(copies),
        "chunk": chunk,
    }


def run_packed_ring():
    """The HalfCheetah-wide ring as its owner holds it, two rows to a line:
    1.25 rings' worth of seeded rows through the ingest path (staging ring,
    super-blocks of 1 to 8 blocks, the donated whole-line insert), then
    every row against the host's copy, through `device_state()[0][idx]`
    inside a jit and outside; and no `copy` or `transpose` of the ring's
    size in the insert or in a read of one chunk's rows."""
    import jax
    import numpy as np
    from ring_layout_util import assert_reads_back, fill_past_a_wrap, ring_sized_copies

    from distributed_ddpg_tpu.parallel.mesh import make_mesh
    from distributed_ddpg_tpu.replay.device import DeviceReplay, PackedRing

    replay = DeviceReplay(
        PACKED_RING_ROWS, OBS, ACT, mesh=make_mesh(-1, 1), block_size=INGEST_BLOCK
    )
    storage = replay.storage
    check(isinstance(storage, PackedRing), f"packed ring: held as {type(storage).__name__}")
    lines = storage.lines.shape
    block = np.zeros((INGEST_BLOCK, replay.width), np.float32)
    idx = np.zeros((800, 64), np.int32)
    programs = {
        "jit_ring_insert": replay._insert.lower(storage, block, replay.ptr, replay.size),
        "read": jax.jit(lambda s, i: s[i]).lower(storage, idx),
    }
    for name, lowered in programs.items():
        found = ring_sized_copies(lowered.compile().as_text(), lines)
        check(not found, f"packed ring: {name} copies the whole ring: {found}")
    n_rows = 1221 * INGEST_BLOCK  # 1.25 rings
    want = fill_past_a_wrap(replay, n_rows, 7 * INGEST_BLOCK)
    try:
        assert_reads_back(replay, want, n_idx=51_200)
    except AssertionError as e:
        raise SmokeFailure(f"packed ring: a row read back differs from the host's: {e}")
    snap = replay.ingest_snapshot()
    check(
        snap["replay_ring_layout"] == "packed" and snap["replay_row_bytes_device"] == 256,
        f"packed ring: {snap['replay_ring_layout']}, {snap['replay_row_bytes_device']} B a row",
    )
    return {
        "lines": list(lines),
        "format": str(replay.storage.lines.format.layout),
        "layout": snap["replay_ring_layout"],
        "row_bytes_device": snap["replay_row_bytes_device"],
        "rows_ingested": n_rows,
        "coalesce_mean": snap["ingest_coalesce_mean"],
    }


def run_chunk_front(n_devices):
    """ops/chunk_front.py on the chip. (a) A ring of each cell's size in
    ring_format's layout, every value a function of its row and column that
    the host computes too; one launch's rows (row 0, the last row and
    duplicates among them) through `cut_rows(storage[idx])` against the
    host's cut of the host's rows: float32 fields bit for bit, and the
    rounded ones equal to the host's rounding to nearest even. (b) Two SAC
    learners at Humanoid width, one built with `unpack_batch` in front and
    one as the rule builds it here, one launch each from the same state, key
    and ring: the same TrainState and TD errors to the last bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    from ring_layout_util import HUMANOID_ACT, HUMANOID_OBS, HUMANOID_SCALE, SAC_HUMANOID_FLAGS

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.ops import chunk_front
    from distributed_ddpg_tpu.parallel.learner import ShardedLearner, resolve_learner_chunk
    from distributed_ddpg_tpu.parallel.mesh import make_mesh
    from distributed_ddpg_tpu.replay.device import DeviceReplay, ring_format
    from distributed_ddpg_tpu.types import packed_width, unpack_batch

    def value(h):  # 24 hashed bits as a float32 in [-0.5, 0.5): exact on both sides
        return (h >> 8).astype(np.float32) * np.float32(2.0**-24) - np.float32(0.5)

    facts = {}
    one = SingleDeviceSharding(jax.devices()[0])
    for name, (capacity, obs, act, K, B) in FRONT_SHAPES.items():
        width = packed_width(obs, act)

        def fill():
            r = jax.lax.broadcasted_iota(jnp.uint32, (capacity, width), 0)
            c = jax.lax.broadcasted_iota(jnp.uint32, (capacity, width), 1)
            return value((r * jnp.uint32(width) + c) * jnp.uint32(2654435761))

        storage = jax.jit(fill, out_shardings=ring_format(one, width))()
        idx = np.random.default_rng(42).integers(0, capacity, (K, B)).astype(np.int32)
        idx[0, :5] = [0, capacity - 1, 7, 7, 0]
        cells = idx.reshape(-1, 1).astype(np.uint32) * np.uint32(width) + np.arange(width, dtype=np.uint32)
        host = value(cells * np.uint32(2654435761)).reshape(K, B, width)  # uint32 wraps, as on the device
        want = unpack_batch(host, obs, act)
        for rounded in (True, False):
            got = jax.jit(
                lambda s, i: chunk_front.cut_rows(s[i], obs, act, rounded)
            )(storage, jax.device_put(idx, one))
            for field in want._fields:
                w, g = getattr(want, field), np.asarray(getattr(got, field))
                if rounded and field in ("obs", "action", "next_obs"):
                    w = w.astype(jnp.bfloat16).astype(np.float32)
                check(
                    g.dtype == np.float32 and np.array_equal(g.view(np.uint32), w.view(np.uint32)),
                    f"front: {name} ring, rounded={rounded}: {field} differs from the host's rows",
                )
        facts[name] = {"ring": [capacity, width], "rows_read": K * B, "format": str(storage.format.layout)}
        del storage, got

    cfg = DDPGConfig.from_flags(SAC_HUMANOID_FLAGS + [f"--replay_capacity={RING_ROWS}"])
    chunk = resolve_learner_chunk(cfg)
    mesh = make_mesh(-1, 1)
    replay = DeviceReplay(RING_ROWS, HUMANOID_OBS, HUMANOID_ACT, mesh=mesh, block_size=INGEST_BLOCK)
    replay.add_packed(np.random.default_rng(9).standard_normal((64 * INGEST_BLOCK, replay.width)).astype(np.float32))
    replay.flush()

    def launch(front):
        rule = chunk_front.front_for
        if front == "xla":
            chunk_front.front_for = lambda **seen: "xla"
        try:
            learner = ShardedLearner(cfg, HUMANOID_OBS, HUMANOID_ACT, HUMANOID_SCALE, mesh=mesh, chunk_size=chunk)
        finally:
            chunk_front.front_for = rule
        check(learner.chunk_front == front, f"front: the rule gave {learner.chunk_front!r} for {front!r}")
        out = learner.run_sample_chunk(replay)
        return jax.device_get((learner.state, out.td_errors)), learner.chunk_hlo()

    (state_x, td_x), _ = launch("xla")
    (state_c, td_c), text = launch("cut")
    check("tpu_custom_call" in text, "front: the cut front's chunk holds no Mosaic custom call")
    leaves_x, leaves_c = jax.tree.leaves(state_x), jax.tree.leaves(state_c)
    same = [np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(leaves_x, leaves_c)]
    check(all(same) and len(leaves_x) == len(leaves_c), f"front: {same.count(False)} of {len(same)} state leaves differ between the fronts")
    check(np.array_equal(td_x.view(np.uint32), td_c.view(np.uint32)), "front: TD errors differ between the fronts")
    check(np.isfinite(td_c).all() and float(np.abs(td_c).max()) > 0, "front: the launch's TD errors are not finite or all zero")
    facts["state_parity"] = {"leaves": len(same), "updates": chunk, "rows": int(td_c.size), "n_devices": n_devices}
    return facts


def kernel_lowering(chunk):
    """What the default leg's kernel lowers to on this backend, read from
    the lowered program text: 'tpu_custom_call' is Mosaic, anything else
    (interpret mode lowers to plain HLO) is not the compiled kernel."""
    import jax
    import jax.numpy as jnp

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.learner import init_train_state
    from distributed_ddpg_tpu.ops import fused_chunk
    from distributed_ddpg_tpu.types import packed_width

    cfg = DDPGConfig.from_flags(FLAGS)
    run = fused_chunk.make_fused_chunk_fn(cfg, OBS, ACT, 1.0, chunk_size=chunk)
    state = init_train_state(cfg, OBS, ACT, cfg.seed)
    batches = jnp.zeros((chunk, cfg.batch_size, packed_width(OBS, ACT)), jnp.float32)
    text = jax.jit(run).lower(state, batches).as_text()
    return "tpu_custom_call" if "tpu_custom_call" in text else "not-mosaic"


def run_leg(name, extra, n_devices):
    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.train import train

    t0 = time.monotonic()
    s = train(DDPGConfig.from_flags(FLAGS + extra))
    wall = time.monotonic() - t0

    check(s["platform"] == "tpu", f"{name}: trainer ran on {s['platform']!r}")
    check(s["n_devices"] == n_devices, f"{name}: trainer saw {s['n_devices']} devices")
    kernel = s["fused_chunk_active"]
    if name == "default":
        check(kernel, "default leg: the megakernel was not selected")
        leg = "kernel" if n_devices == 1 else "fused-mesh"
    else:
        check(not kernel, "scan leg: the megakernel ran under --fused_chunk=off")
        leg = "scan"
    chunk = s["learner_chunk"]
    check(
        s["learner_steps"] >= 3 * chunk,
        f"{name}: {s['learner_steps']} learner steps < 3 chunks of {chunk}",
    )
    for key in ("critic_loss", "actor_loss"):
        check(
            key in s and math.isfinite(s[key]),
            f"{name}: last chunk's {key} is {s.get(key)!r}",
        )
    check(
        s["param_checksum"] != s["param_checksum_start"]
        and math.isfinite(s["param_checksum"]),
        f"{name}: actor params did not move ({s['param_checksum']})",
    )
    # Every env step handed to the replay is in the ring or still staged on
    # the host; the only surplus is the warmup flush's padding.
    surplus = s["buffer_fill"] + s["ingest_queue_rows"] - s["env_steps"]
    check(
        0 <= surplus < 2 * INGEST_BLOCK,
        f"{name}: ring {s['buffer_fill']} + staged {s['ingest_queue_rows']} "
        f"vs {s['env_steps']} env steps",
    )
    check(s["actor_respawns"] == 0, f"{name}: {s['actor_respawns']} actor respawns")
    for flag in ("preempted", "pod_degraded", "numeric_failed"):
        check(not s[flag], f"{name}: run reported {flag}")
    # Placement from sharding metadata: state and ring on every chip.
    check(s["mesh_data_axis"] == n_devices, f"{name}: mesh_data_axis {s['mesh_data_axis']}")
    for what in ("state_devices", "replay_devices"):
        check(s[what] == n_devices, f"{name}: {what} {s[what]} of {n_devices}")
    return {
        "leg": leg,
        "fused_chunk_active": kernel,
        "chunk": chunk,
        "learner_steps": s["learner_steps"],
        "env_steps": s["env_steps"],
        "buffer_fill": s["buffer_fill"],
        "critic_loss": round(s["critic_loss"], 6),
        "actor_loss": round(s["actor_loss"], 6),
        "first_chunk_s": round(s["first_chunk_s"], 3),
        "steady_s": round(s["steady_s"], 3),
        "wall_s": round(wall, 1),
        "mesh": [s["mesh_data_axis"], s["mesh_model_axis"]],
        "state_devices": s["state_devices"],
        "replay_devices": s["replay_devices"],
    }


def run_checks(device, cache_dir):
    import jax
    import jaxlib
    import libtpu

    from distributed_ddpg_tpu import native

    n_devices = device["count"]
    facts = {
        "device": device,
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": libtpu.__version__,
        },
        "compile_cache": {
            "dir": cache_dir,
            "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            "entries_before": cache_entries(cache_dir),
        },
        "native_replay_core": native.available(),
    }
    check(facts["native_replay_core"], "the C++ replay core did not build (g++?)")

    # The parity and ring bodies are the tests' own (tests/*_util.py).
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests"))
    t0 = time.monotonic()
    facts["parity"] = run_parity()
    facts["legs"] = {name: run_leg(name, extra, n_devices) for name, extra in LEGS}
    facts["kernel_lowering"] = kernel_lowering(facts["legs"]["default"]["chunk"])
    check(
        facts["kernel_lowering"] == "tpu_custom_call",
        "the kernel did not lower to a Mosaic custom call",
    )
    facts["ring"] = run_ring_layout()
    facts["packed_ring"] = run_packed_ring()
    facts["chunk_front"] = run_chunk_front(n_devices)
    facts["compile_cache"]["entries_after"] = cache_entries(cache_dir)
    left = mp.active_children()
    for p in left:
        p.terminate()
    check(not left, f"{len(left)} child process(es) outlived the trainer")
    facts["wall_s"] = round(time.monotonic() - t0, 1)
    return facts


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs the chip — JAX resolved platform "
            f"{dev.platform!r} (jax_platforms={jax.config.jax_platforms!r})",
            file=sys.stderr,
        )
        return 2
    n_devices = len(jax.devices())

    # Importing the mesh module places the compile cache (env var, or
    # <checkout>/.jax_cache); count entries before anything compiles.
    from distributed_ddpg_tpu.parallel import mesh  # noqa: F401

    cache_dir = jax.config.jax_compilation_cache_dir
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": n_devices}
    try:
        facts = run_checks(device, cache_dir)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        print(verdict_line(False, device), flush=True)
        return 1
    print(json.dumps({"facts": facts}), flush=True)
    print(verdict_line(True, device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
