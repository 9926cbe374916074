"""The replay ring's device layout (replay/device.py ring_layout, PackedRing,
ring_format): the width rule, that the row-major Format is the TPU's alone,
and that no program that takes the ring copies it — compiled here for the
CPU and, through the TPU compiler the sandbox carries, for a described v5e.
What a packed ring holds and reads back: tests/test_packed_ring.py."""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_ddpg_tpu.replay.device import (
    DeviceReplay,
    PackedRing,
    _RingProgram,
    ring_format,
    ring_layout,
    ring_row_bytes,
    ring_write,
)
from ring_layout_util import assert_reads_back, fill_past_a_wrap, humanoid_ring_programs, ring_sized_copies


@pytest.mark.parametrize(
    "width,layout,row_bytes",
    [
        (10, "packed", 42),        # Pendulum: 12 rows to a line
        (33, "packed", 170),       # three to a line, x1.07 of compact's 160
        (43, "packed", 256),       # HalfCheetah: two to a line, x1.33 of compact's 192, the worst case
        (64, "packed", 256),       # two to a line, nothing wasted
        (65, "compact", 288),      # Ant: one row a line would be x1.78
        (120, "row_major", 512),
        (128, "row_major", 512),
        (772, "row_major", 3584),  # Humanoid: 772 -> 896, +15.5%
        (1027, "row_major", 4608),
    ],
)
def test_width_rule(width, layout, row_bytes):
    assert ring_layout(width) == layout
    assert ring_row_bytes(width, layout) == row_bytes
    # A row-sharded ring is never packed: its narrow rows stay compact.
    assert ring_layout(width, sharded=True) == ("compact" if layout == "packed" else layout)
    # The compact row pads to 8 sublanes, whatever the rule picked.
    assert ring_row_bytes(width, "compact") == 4 * (-(-width // 8) * 8)


def test_plain_sharding_off_the_tpu():
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    sharding = NamedSharding(mesh, P(None, None))
    # Humanoid's width picks row-major on the TPU; here the helper hands the
    # sharding back untouched, and no mesh stays no sharding.
    assert ring_format(sharding, 772) is sharding
    assert ring_format(sharding, 43) is sharding  # packed lines: the default layout, on the TPU too
    assert ring_format(None, 772) is None
    r = DeviceReplay(64, 376, 17, mesh=mesh, block_size=16)
    assert r.storage_format == sharding and not isinstance(r.storage_format, Format)
    assert r.storage.sharding == sharding
    snap = r.ingest_snapshot()
    assert snap["replay_ring_layout"] == "compact"
    assert snap["replay_row_bytes_device"] == 4 * 772
    assert snap["replay_device_storage_bytes"] == 64 * 4 * 772


@pytest.mark.parametrize("with_mesh", [False, True])
def test_fill_wrap_save_restore_rows(with_mesh):
    """Built, filled, wrapped, saved and restored: the rows the ring holds
    are the rows a plain numpy ring holds, bit for bit."""
    mesh = (
        Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        if with_mesh
        else None
    )
    cap, block = 96, 16
    r = DeviceReplay(cap, 376, 17, mesh=mesh, block_size=block, max_coalesce=1)
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((cap + 3 * block, r.width)).astype(np.float32)
    want = np.zeros((cap, r.width), np.float32)
    for at in range(0, len(rows), block):
        r.add_packed(rows[at : at + block])
        want[(at + np.arange(block)) % cap] = rows[at : at + block]
    r.drain_pending()
    assert r.storage.shape == (cap, r.width)
    np.testing.assert_array_equal(np.asarray(r.device_state()[0]), want)
    state = r.state_dict()
    assert int(state["ptr"]) == (3 * block) % cap and int(state["size"]) == cap
    r2 = DeviceReplay(cap, 376, 17, mesh=mesh, block_size=block, max_coalesce=1)
    r2.load_state_dict(state)
    np.testing.assert_array_equal(np.asarray(r2.storage), want)
    assert r2.storage.sharding == r.storage.sharding
    # The restored ring goes on taking inserts where the saved one stopped.
    more = rng.standard_normal((block, r.width)).astype(np.float32)
    r2.add_packed(more)
    r2.drain_pending()
    want[(3 * block + np.arange(block)) % cap] = more
    np.testing.assert_array_equal(np.asarray(r2.storage), want)


def _shard_pointers(array):
    return [shard.data.unsafe_buffer_pointer() for shard in array.addressable_shards]


def test_no_ring_sized_copy_in_the_programs_that_take_the_ring():
    """`jit_ring_insert` and the scan `sample_chunk_fn` at Humanoid width:
    no `copy` or `transpose` with the ring's shape in the optimised HLO
    (chip_smoke.py asserts the same on the v5e at 1.4e6 rows), but for the
    two that XLA:CPU, and only it (the v5e cases below), leaves at the edges
    of the insert's two branches: exactly one in each branch computation of
    the conditional, on the ring as it comes in or as it goes out, and none
    in ENTRY or anywhere else. Neither moves a ring: the program holds one
    ring-sized buffer, the donated one, which is the result's (the alias, the
    temporaries' size, the buffers' addresses before and after an insert
    under each branch), so each is a copy of that buffer onto itself, which
    XLA:CPU does not run (test_cpu_insert_costs_a_block_not_a_ring)."""
    replay, copies = humanoid_ring_programs(capacity=4096, chunk=2)
    shape = replay.storage.shape
    assert shape == (4096, 772)
    assert set(copies) == {"jit_ring_insert", "jit_sample_chunk_fn"}
    assert copies["jit_sample_chunk_fn"] == []

    block = np.zeros((replay.block_size, replay.width), np.float32)
    compiled = replay._insert.lower(replay.storage, block, replay.ptr, replay.size).compile()
    text = compiled.as_text()
    comps = _computations(text)
    entry = re.search(r"^ENTRY %?([\w.\-]+) ", text, re.M).group(1)
    (branches,) = re.findall(r"conditional\(.*branch_computations=\{([^}]*)\}", "\n".join(comps[entry]))
    branches = [b.strip().lstrip("%") for b in branches.split(",")]
    assert len(branches) == 2
    found = {name: ring_sized_copies("\n".join(lines), shape) for name, lines in comps.items()}
    assert {name: len(at) for name, at in found.items() if at} == {branches[0]: 1, branches[1]: 1}
    assert sorted(copies["jit_ring_insert"]) == sorted(found[branches[0]] + found[branches[1]])
    for branch in branches:
        (line,) = [l for l in comps[branch] if re.search(r"\] copy\(|\} copy\(", l)]
        name, operand = re.match(r"\s*%?([\w.\-]+) = \S+ copy\(%?([\w.\-]+)\)", line).groups()
        (root,) = [l for l in comps[branch] if l.lstrip().startswith("ROOT ")]
        ring_out = re.search(r"\(%?" + re.escape(name) + r"\)", root) is not None
        ring_in = any(re.match(r"\s*%?" + re.escape(operand) + r" = \S+ get-tuple-element\(", l) for l in comps[branch])
        assert ring_in != ring_out, line
    assert "input_output_alias={ {0}: (0, {}, may-alias) }" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4096 * 4 * 772 // 8

    at = _shard_pointers(replay.storage)
    for m in (1024, 1024, 1024, 1536):  # three runs, then a block that passes the ring's end
        replay.insert_device_rows(jnp.ones((m, replay.width), jnp.float32))
        assert _shard_pointers(replay.storage) == at
    snap = replay.ingest_snapshot()
    assert (snap["replay_insert_runs"], snap["replay_insert_wrapped"]) == (3, 1)


def test_cpu_insert_costs_a_block_not_a_ring():
    """What the two `copy` instructions of the test above cost when the
    program runs: nothing. An insert of 256 rows into a ring of 202 MB,
    under either branch, against one copy of that ring in the same process
    (0.3 ms against 137 ms on an idle sandbox; the bound leaves load its room:
    the best of ten against the best of three, and a factor of ten)."""
    import time

    cap = 65536
    r = DeviceReplay(cap, 376, 17, block_size=256)
    assert r.ring_layout == "compact"
    rows = jnp.ones((256, r.width), jnp.float32)

    def best_of(n, run):
        took = []
        for _ in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(run())
            took.append(time.perf_counter() - t0)
        return min(took)

    def insert_at(ptr):
        ptr = jax.device_put(np.int32(ptr), r.ptr.sharding)

        def run():
            r.storage, _, _ = r._insert(r.storage, rows, ptr, r.size)
            return r.storage

        return run

    insert_at(0)()  # compiled
    a_copy = best_of(3, lambda: jnp.copy(r.storage))
    assert 10 * best_of(10, insert_at(1000)) < a_copy  # a run
    assert 10 * best_of(10, insert_at(cap - 100)) < a_copy  # passes the ring's end
    landed = np.zeros(cap, bool)
    landed[np.r_[0:256, 1000:1256, cap - 100 : cap]] = True  # 0, 1000 and cap - 100: the last one's 156 wrapped rows lie in the first's
    np.testing.assert_array_equal(np.asarray(r.storage[:, 0]), landed.astype(np.float32))


def test_ring_sized_copies_reads_hlo_text():
    text = "\n".join(
        [
            "  %copy.3 = f32[4096,772]{1,0:T(8,128)} copy(%storage.1), sharding={replicated}",
            "  ROOT %transpose.9 = f32[4096,772]{0,1:T(8,128)} transpose(%fusion)",
            "  %copy.4 = f32[1024,772]{1,0:T(8,128)} copy(%block.1)",
            "  %fusion = f32[4096,772]{1,0:T(8,128)} fusion(%copy.3, %copy.4), kind=kCustom",
        ]
    )
    assert ring_sized_copies(text, (4096, 772)) == [
        "copy.3 = f32[4096,772]{1,0:T(8,128)} copy",
        "transpose.9 = f32[4096,772]{0,1:T(8,128)} transpose",
    ]


@pytest.fixture
def persistent_cache(tmp_path):
    """A persistent compile cache of this test's own that keeps every
    program, as the benchmark harness's does."""
    from jax.experimental.compilation_cache import compilation_cache

    names = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_enable_compilation_cache",
    )
    before = {name: getattr(jax.config, name) for name in names}
    for name, value in zip(names, (str(tmp_path), 0, -1, True)):
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    yield tmp_path
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def test_ring_program_never_touches_the_persistent_cache(persistent_cache):
    """Where the ring is row-major, the programs that return it go through
    _RingProgram: compiled per argument shapes with the persistent cache
    switched off around the compile (an executable loaded from it labels its
    outputs with the default layout) and switched back on after."""

    def bump(ring, block, by):
        return ring.at[: block.shape[0]].set(block + by), ring.sum()

    def entries():
        return sorted(f.name for f in persistent_cache.iterdir() if "bump" in f.name)

    jitted = jax.jit(bump, donate_argnums=(0,))
    program = _RingProgram(jitted)
    assert program.lower == jitted.lower
    for rows in (2, 4, 2):  # one executable per block shape, reused after
        ring, total = program(jnp.ones((8, 3)), jnp.zeros((rows, 3)), 2.0)
        want = np.ones((8, 3), np.float32)
        want[:rows] = 2.0
        np.testing.assert_array_equal(np.asarray(ring), want)
        assert float(total) == 24.0
    assert len(program._compiled) == 2
    assert entries() == []
    assert jax.config.jax_enable_compilation_cache is True
    # A program of another shape through the plain jit does land in the
    # cache: the switch was put back, and the cache was live all along.
    jitted(jnp.ones((16, 3)), jnp.zeros((2, 3)), 2.0)
    assert len(entries()) >= 1


def test_ring_program_only_where_the_ring_is_row_major():
    r = DeviceReplay(64, 376, 17, block_size=16)
    jitted = jax.jit(lambda x: x)
    assert r.ring_program(jitted) is jitted  # off the TPU: the jit itself
    assert not isinstance(r._insert, _RingProgram)
    packed = DeviceReplay(64, 17, 6, block_size=16)
    assert packed.ring_layout == "packed" and packed.storage_format is None
    assert packed.ring_program(jitted) is jitted  # packed lines: the jit itself everywhere


# --- the plain ring's insert (ring_write): a block in ring order that ends
# inside the ring is one dynamic-update-slice, and only a block that passes
# the ring's end is scattered (PERF.md PR 41). The rows land where the
# scatter of computed indices put them, which is written out here. ---

RUN_CAP = 200
_ring_write = jax.jit(ring_write, donate_argnums=(0,))


@pytest.mark.parametrize("width", [65, 240, 772])
@pytest.mark.parametrize("m", [1, 8, 96, RUN_CAP])
@pytest.mark.parametrize("at", ["0", "8", "13", "exact_fit", "wraps_by_one_row", "last_row"])
def test_ring_write_puts_every_row_where_the_scatter_put_it(width, m, at):
    ptr = {
        "0": 0, "8": 8, "13": 13, "exact_fit": RUN_CAP - m, "wraps_by_one_row": RUN_CAP - m + 1, "last_row": RUN_CAP - 1,
    }[at] % RUN_CAP
    rng = np.random.default_rng(width * 1000 + m)
    storage = rng.standard_normal((RUN_CAP, width)).astype(np.float32)
    block = rng.standard_normal((m, width)).astype(np.float32)
    want = jnp.asarray(storage).at[(ptr + jnp.arange(m)) % RUN_CAP].set(block)
    got = _ring_write(jnp.asarray(storage), jnp.asarray(block), jnp.int32(ptr))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("with_mesh", [False, True])
@pytest.mark.parametrize("obs_dim,act_dim,layout", [(376, 17, "compact"), (17, 6, "packed")])
def test_device_rows_three_times_round_a_small_ring(obs_dim, act_dim, layout, with_mesh):
    """insert_device_rows in blocks that do not divide the ring: rows,
    pointer, size and the host's counts of what each insert did, against a
    numpy ring. A packed ring has its own whole-line write and counts no run."""
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model")) if with_mesh else None
    cap, m = 64, 24
    r = DeviceReplay(cap, obs_dim, act_dim, mesh=mesh, block_size=16)
    assert r.ring_layout == layout
    rng = np.random.default_rng(3)
    want, ptr, wrapped = np.zeros((cap, r.width), np.float32), 0, 0
    for i in range(1, 12):  # 264 rows: four times round and a bit
        rows = rng.standard_normal((m, r.width)).astype(np.float32)
        want[(ptr + np.arange(m)) % cap] = rows
        wrapped += ptr + m > cap
        ptr = (ptr + m) % cap
        assert r.insert_device_rows(jnp.asarray(rows)) == m
        snap = r.ingest_snapshot()
        assert (int(r.ptr), len(r)) == (ptr, min(i * m, cap))
        assert snap["ring_wraps"] == i * m // cap
        assert snap["replay_insert_wrapped"] == wrapped
        assert snap["replay_insert_runs"] == (0 if layout == "packed" else i - wrapped)
        np.testing.assert_array_equal(np.asarray(r.storage), want)
    assert wrapped == 3  # 40 + 24 = 64 is an exact fit: a run, not a wrap


def test_host_ships_count_their_runs_and_wraps():
    """The shipper's super-blocks go through the same insert and the same
    host arithmetic, tracked sources or not."""
    r = DeviceReplay(96, 376, 17, block_size=16, max_coalesce=4)
    want = fill_past_a_wrap(r, 160, push=40)
    assert_reads_back(r, want)
    snap = r.ingest_snapshot()
    assert snap["ring_wraps"] == 1
    assert snap["replay_insert_runs"] + snap["replay_insert_wrapped"] == snap["ingest_ship_calls"]
    assert snap["replay_insert_wrapped"] <= 1 and snap["replay_insert_runs"] >= 3


def _primitives(jaxpr):
    return [eqn.primitive.name for eqn in jaxpr.eqns]


def test_plain_insert_is_a_slice_update_and_scatters_only_under_the_wraps_branch():
    r = DeviceReplay(4096, 376, 17, block_size=256)
    block = jnp.zeros((256, r.width), jnp.float32)
    args = (r.storage, block, r.ptr, r.size)
    top = jax.make_jaxpr(r._insert_pure)(*args).jaxpr
    assert _primitives(top).count("cond") == 1 and "scatter" not in _primitives(top)
    (cond,) = [eqn for eqn in top.eqns if eqn.primitive.name == "cond"]
    wraps, fits = (_primitives(branch.jaxpr) for branch in cond.params["branches"])
    assert "scatter" in wraps and "dynamic_update_slice" not in wraps
    assert "dynamic_update_slice" in fits and "scatter" not in fits
    lowered = r._insert.lower(*args)
    text = lowered.as_text()
    assert text.count("stablehlo.dynamic_update_slice") == 1 and text.count('"stablehlo.scatter"') == 1
    assert "stablehlo.case" in text
    # The donated ring comes back in the buffer it went in with.
    compiled = lowered.compile()
    assert "input_output_alias={ {0}: (0, {}, may-alias) }" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4096 * 4 * r.width // 8
    # Not a run: a permuted block, a block longer than the ring, a packed ring.
    permuted = jax.make_jaxpr(lambda s, b, p: ring_write(s, b, p, jnp.arange(256)[::-1]))(r.storage, block, r.ptr)
    longer = jax.make_jaxpr(ring_write)(jnp.zeros((128, r.width)), block, r.ptr)
    for jaxpr in (permuted, longer):
        assert "scatter" in _primitives(jaxpr.jaxpr) and "cond" not in _primitives(jaxpr.jaxpr)
    packed = DeviceReplay(4096, 17, 6, block_size=256)
    packed_jaxpr = jax.make_jaxpr(packed._insert_pure)(
        packed.storage, jnp.zeros((256, packed.width)), packed.ptr, packed.size
    )
    assert "cond" not in _primitives(packed_jaxpr.jaxpr)


@pytest.mark.parametrize("name", ["replay.insert.packed", "replay.insert.sharded", "replay.insert.devrows.sharded"])
def test_the_inserts_that_are_no_runs_hold_no_branch(name):
    """PackedRing.write and the sharded inserts do not go through
    ring_write's plain branch: the registry's programs hold their scatter
    at the top, under no `cond`, and no slice update (their lowered text is
    the parent's to the bit: PERF.md PR 41 has the hashes)."""
    from distributed_ddpg_tpu.replay.device import program_specs

    built = next(s for s in program_specs() if s.name == name).build()
    text = built.fn.lower(*built.args).as_text()
    assert "stablehlo.case" not in text and "stablehlo.dynamic_update_slice" not in text
    assert '"stablehlo.scatter"' in text


# --- compiled for a described v5e: the layout itself. The TPU's compiler is
# installed in the sandbox and compiles for a chip that is described, not
# attached; nothing runs. The topology is described inside a fixture (never
# at import: one process at a time may load libtpu), and this is the one
# test file that does so. ---

CAPACITY = 1_000_000  # rows, the papers' ring: too large for XLA to move into faster memory


# --- the megakernel's static schedule (tools/kernel_bundles.py): the TPU
# compiler's own count of an update's VLIW bundles, for each kernel cell's
# configuration. The dumper aborts the process it runs in, so each compile
# is a CHILD's, and a child can load the TPU library only while no other
# process of this run holds it: these cases stand ABOVE the first test that
# asks for v5e_sharding, which loads the library into this process for good
# (xdist hands the file to one worker, in file order). ---

_TPU_LIBRARY_HELD = []  # v5e_sharding appends once this process has loaded libtpu


def _static_phases(name):
    """tools/kernel_bundles.phases of configuration `name`'s kernel, compiled
    for a described v5e in a child process (or a skip where that cannot be)."""
    import importlib.util
    import os
    import tempfile

    from distributed_ddpg_tpu.tools import kernel_bundles as kb

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU compiler here: nothing to compile for")
    if _TPU_LIBRARY_HELD and not os.environ.get("ALLOW_MULTIPLE_LIBTPU_LOAD"):
        pytest.skip("this process already holds the TPU library: run the file from its top")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as directory:  # ~330 MB of compiler text, gone with the case
        kb.compile_and_dump(os.path.join(root, "benchmarks", "configs", name + ".json"), directory)
        return kb.phases(kb.load_dump(directory).bundles)


@pytest.mark.parametrize(
    "name,update_ceiling,delayed_ceiling",
    [
        # an update's bundles outside any branch / under the largest branch
        # (TD3's delayed actor and targets; nothing of that size elsewhere).
        # PR 34 read 4,290, 11,255 + 2,723 and 16,562 with each net's output
        # layer lane-major; with [F, out] heads 5,280, 12,350 + 3,363, 17,279.
        ("ddpg-halfcheetah", 4500, 200),
        ("td3-halfcheetah", 11700, 2900),
        ("d4pg-halfcheetah", 16800, 200),
    ],
)
def test_megakernel_static_update_stays_under_its_ceiling(name, update_ceiling, delayed_ceiling):
    """A later PR that pads a head again, or spills a loop, learns so here
    and not on the chip. Bundles are issue cycles, not time."""
    ph = _static_phases(name)
    delayed = max((n for _, n in ph["branched"]), default=0)
    assert ph["update"] - delayed <= update_ceiling, ph
    assert delayed <= delayed_ceiling, ph


def test_pixel_crop_row_stays_under_its_ceiling():
    """The pixel cell's kernel (ops/pixels.py: the crop of an update's 256
    images, a channel of 128 a grid step) is a loop over an image's 84 rows,
    six a trip: 197 bundles a trip at PR 48 (a row: nine selects of three
    vregs, four sublane windows, the funnel shift, four strided stores; one
    row a trip was 73, and 1.6% of the launch slower on the chip). Bundles
    are issue cycles, not time."""
    loops = _static_phases("drqv2-humanoid")["loops"]
    assert len(loops) == 1 and loops[0] <= 230, loops


@pytest.fixture(scope="module")
def v5e_sharding():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    _TPU_LIBRARY_HELD.append(True)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    return NamedSharding(mesh, P(None, None))


def _compiled_for(sharding, width, fmt):
    """(insert, gather) executables for a ring of CAPACITY x width whose
    parameter and result are held in `fmt`: packed lines where the rule packs,
    the plain array otherwise. The insert is the ring's own (DeviceReplay's
    ring_insert over ring_write), written out here so that no CAPACITY-row
    ring is allocated on the CPU to borrow it from."""

    def ring_insert(storage, block, ptr, size):
        m = block.shape[0]
        storage = ring_write(storage, block, ptr)
        return storage, (ptr + m) % CAPACITY, jnp.minimum(size + m, CAPACITY)

    insert = ring_insert
    replicated = NamedSharding(sharding.mesh, P())
    if ring_layout(width) == "packed":
        lines = (PackedRing.n_lines(width, CAPACITY), 128)
        ring = PackedRing(jax.ShapeDtypeStruct(lines, jnp.float32, sharding=fmt), width, CAPACITY)
    else:
        ring = jax.ShapeDtypeStruct((CAPACITY, width), jnp.float32, sharding=fmt)
    block = jax.ShapeDtypeStruct((1024, width), jnp.float32, sharding=sharding)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated)
    idx = jax.ShapeDtypeStruct((8, 256), jnp.int32, sharding=replicated)
    inserted = jax.jit(
        insert, donate_argnums=(0,), out_shardings=(fmt, replicated, replicated)
    ).lower(ring, block, scalar, scalar).compile()
    gathered = jax.jit(lambda storage, i: storage[i]).lower(ring, idx).compile()
    return inserted, gathered


def _ring_sized_ops(hlo_text, shape):
    """Opcodes of the instructions of the ENTRY computation whose result has
    the ring's shape."""
    entry = hlo_text.split("ENTRY ", 1)[1]
    rows, width = shape
    pat = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = f32\[" + f"{rows},{width}" + r"\]\{[^}]*\} ([\w\-]+)\(")
    return sorted(m.group(1) for m in map(pat.match, entry.splitlines()) if m)


@pytest.mark.parametrize("width", [43, 772])
def test_v5e_programs_hold_no_ring_sized_copy(v5e_sharding, width):
    fmt = ring_format(v5e_sharding, width)
    if width == 772:
        assert isinstance(fmt, Format) and fmt.sharding is v5e_sharding
        assert fmt.layout.major_to_minor == (0, 1)
        assert fmt.layout.tiling == ((8, 128),)
    else:
        # Packed lines and compact rows name no layout: the runtime's own, so
        # their programs are plain jits (ring_program) and go through the
        # persistent cache like any other.
        assert fmt is v5e_sharding
    inserted, gathered = _compiled_for(v5e_sharding, width, fmt)
    shape = (CAPACITY // 2, 128) if width == 43 else (CAPACITY, width)
    assert ring_sized_copies(inserted.as_text(), shape) == []
    assert ring_sized_copies(gathered.as_text(), shape) == []
    # The donated insert hands the ring back in the layout it came in (the
    # ring is the first leaf of the arguments and of the results).
    ring_in = jax.tree.leaves(inserted.input_formats)[0].layout
    assert jax.tree.leaves(inserted.output_formats)[0].layout == ring_in
    # One ring in HBM while the insert runs, not two.
    assert inserted.memory_analysis().temp_size_in_bytes < CAPACITY * 4 * width // 8
    if width == 772:
        assert ring_in == fmt.layout
    if width == 43:
        # The runtime's own layout of [CAPACITY // 2, 128] is the row-major
        # one. If a later compiler changes that, the rule must name a Format.
        assert ring_in.major_to_minor == (0, 1) and ring_in.tiling == ((8, 128),)
        assert jax.tree.leaves(gathered.input_formats)[0].layout == ring_in
        # No op of the ring's size but the insert's one in-place scatter: the
        # lines touched are gathered, overlaid and scattered back whole.
        assert _ring_sized_ops(inserted.as_text(), shape) == ["fusion", "parameter"]
        assert _ring_sized_ops(gathered.as_text(), shape) == ["parameter"]
        assert inserted.memory_analysis().temp_size_in_bytes < 2**20
        assert gathered.memory_analysis().temp_size_in_bytes < 2**21  # the [8 x 256, 128] lines gathered


@pytest.mark.parametrize("width", [240, 772])
def test_v5e_plain_insert_holds_its_scatter_under_the_wraps_branch(v5e_sharding, width):
    """The row-major ring's insert as the chip's compiler leaves it (240
    floats a row: the PQL cell's ring): ENTRY holds the `conditional` and no
    op of the ring's size of its own; one branch is the in-place
    dynamic-update-slice, the other the scatter; the ring's buffer is the
    result's, with no second ring beside it."""
    fmt = ring_format(v5e_sharding, width)
    assert isinstance(fmt, Format)
    inserted, _ = _compiled_for(v5e_sharding, width, fmt)
    text = inserted.as_text()
    assert "input_output_alias={ {0}: (0, {}, may-alias) }" in text
    assert inserted.memory_analysis().temp_size_in_bytes < CAPACITY * 4 * width // 8
    assert not {"scatter", "fusion", "copy", "dynamic-update-slice"} & set(_ring_sized_ops(text, (CAPACITY, width)))
    comps = _computations(text)
    entry = text.split("ENTRY ", 1)[1].split("\n}", 1)[0]
    (branches,) = re.findall(r"conditional\(.*branch_computations=\{([^}]*)\}", entry)
    found = []
    for name in (b.strip().lstrip("%") for b in branches.split(",")):
        lines = [l for c in {name} | _called_from(comps, name) for l in comps[c]]
        found.append((any(" dynamic-update-slice(" in l for l in lines), any(" scatter(" in l for l in lines)))
    assert sorted(found) == [(False, True), (True, False)]


def test_v5e_default_layout_is_what_the_rule_replaces(v5e_sharding):
    """The finding the rule rests on: left to the runtime, the Humanoid-wide
    ring lies feature-major and XLA re-lays it whole, twice an insert and
    once a gather. If a later compiler stops, the rule can go."""
    inserted, gathered = _compiled_for(v5e_sharding, 772, v5e_sharding)
    assert inserted.input_formats[0][0].layout.major_to_minor == (1, 0)
    shape = (CAPACITY, 772)
    assert len(ring_sized_copies(inserted.as_text(), shape)) == 2
    assert len(ring_sized_copies(gathered.as_text(), shape)) == 1


# --- the megakernel's chunk, compiled for the same described v5e (this is
# the one file that describes the topology, so the guard lives here): a
# configuration's net either fits Mosaic's default scoped-VMEM limit at its
# committed widths and batch, or a later PR learns so here and not on the
# chip. ---

MIB = 1024 * 1024


@pytest.mark.parametrize(
    "name,limit_mib",
    [
        ("ddpg-halfcheetah", None),
        ("d4pg-halfcheetah", None),  # the program as train() builds it: Mosaic's default, 16 MiB
        # 400-300 at batch 256 x 51 atoms takes 8.38 MiB of scoped VMEM (the
        # smallest limit it compiles under, bisected to 1/16 MiB; 7.88 before
        # its [300, 51] head rode lane-major, PR 34). Five eighths of a MiB
        # of room is kept: a projection loop that spills again (it read 14.60
        # with its operands batch-on-sublanes) fails here, not on the chip.
        ("d4pg-halfcheetah", 9),
        # TD3 at the paper's 400-300, twin critics, batch 100 (no multiple of
        # the 8 sublanes: Mosaic takes the blocks and the batch-contracting
        # dots as they are): 5.93 MiB of state, the largest of the cells', and
        # 8.06 MiB of scoped VMEM (bisected to 1/16 MiB; 9.25 with [F, out]
        # heads), since the kernel's temporaries grow with the batch and not
        # with the state. Under the default as train() builds it, and with
        # fifteen sixteenths of a MiB of room kept.
        ("td3-halfcheetah", None),
        ("td3-halfcheetah", 9),
        # DDPG 2x256 at batch 64: 3.31 MiB (3.56 with [F, out] heads).
        ("ddpg-halfcheetah", 4),
    ],
)
def test_v5e_megakernel_chunk_fits_scoped_vmem(v5e_sharding, monkeypatch, name, limit_mib):
    import json
    import os

    from jax.experimental.pallas import tpu as pltpu

    from distributed_ddpg_tpu.ops import fused_chunk
    from distributed_ddpg_tpu.tools.kernel_bundles import lower_chunk

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    conf = json.load(open(os.path.join(root, "benchmarks", "configs", name + ".json")))
    if limit_mib is not None:
        real = fused_chunk.pl.pallas_call
        monkeypatch.setattr(
            fused_chunk.pl, "pallas_call",
            lambda *a, **kw: real(*a, compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit_mib * MIB), **kw),
        )
    replicated = NamedSharding(v5e_sharding.mesh, P())
    compiled = lower_chunk(conf, replicated).compile()  # raises what the chip's compiler would
    assert "tpu_custom_call" in compiled.as_text()


# --- the scan leg's chunk, compiled for the same described v5e: the
# launch's noise is drawn before the 800-update loop, so the while body the
# chip runs holds no threefry (tests/test_learner_noise.py holds the same of
# every chunk program's jaxpr, and the bits). ---


def _computations(hlo_text):
    """{computation: its instruction lines} of an optimised HLO module."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            cur.append(line)
    return comps


def _called_from(comps, name):
    """The computations `name`'s instructions call, and theirs."""
    seen, stack = set(), [name]
    while stack:
        for line in comps.get(stack.pop(), []):
            for callee in re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line):
                if callee not in seen:
                    seen.add(callee)
                    stack.append(callee)
    return seen


def _v5e_scan_chunk(v5e_sharding, name, chunk):
    """(cfg, state shapes, the compiled executable) of configuration `name`'s
    scan chunk of `chunk` updates at its own widths, built as
    ShardedLearner's scan_steps builds it (scan_chunk over
    learner.chunk_noise; unroll 4) and compiled for the described v5e."""
    import json
    import os

    from distributed_ddpg_tpu import learner as learner_lib
    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel.learner import scan_chunk
    from distributed_ddpg_tpu.types import unpack_batch

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    conf = json.load(open(os.path.join(root, "benchmarks", "configs", name + ".json")))
    cfg = DDPGConfig.from_flags([f for f in conf["flags"] if not f.startswith("--replay_capacity")])
    env = conf["env"]
    obs, act = env["obs_dim"], env["act_dim"]
    assert (obs, act) == (376, 17)
    step = learner_lib.make_learner_step(cfg, env["action_scale"], action_offset=env["action_offset"])

    def run(s, packed):
        noise = learner_lib.chunk_noise(
            cfg, learner_lib.noise_base_key(cfg), s.step, chunk,
            cfg.batch_size, act,
        )
        return scan_chunk(step, s, unpack_batch(packed, obs, act), noise, unroll=4)

    replicated = NamedSharding(v5e_sharding.mesh, P())
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
        jax.eval_shape(lambda: learner_lib.init_train_state(cfg, obs, act, 0)),
    )
    packed = jax.ShapeDtypeStruct((chunk, cfg.batch_size, 2 * obs + act + 3), jnp.float32, sharding=replicated)
    return cfg, state, jax.jit(run, donate_argnums=(0,)).lower(state, packed).compile()


def test_v5e_scan_chunk_draws_its_noise_before_the_loop(v5e_sharding):
    """`sac-humanoid`'s scan chunk at the configuration's own sizes (batch
    256, obs 376, act 17, K 800, unroll 4), built as ShardedLearner's
    scan_steps builds it (_v5e_scan_chunk). With the draw in the step the
    body was 1,539 instructions, 106 of them `xor` and 80
    `shift-left` of key arithmetic, and its two sampling fusions held a
    threefry each, 335 and 512 instructions."""
    cfg, _, compiled = _v5e_scan_chunk(v5e_sharding, "sac-humanoid", 800)
    assert cfg.batch_size == 256 and cfg.sac
    text = compiled.as_text()

    comps = _computations(text)
    whiles = [line for lines in comps.values() for line in lines if re.search(r"\bwhile\(", line)]
    assert len(whiles) == 1
    body = re.search(r"body=%?([\w.\-]+)", whiles[0]).group(1)
    assert 500 < len(comps[body]) < 1100
    assert not [line for line in comps[body] if re.search(r"\b(xor|shift-left)\(", line)]
    drawn = re.compile(r'op_name="[^"]*(threefry|_normal)')
    inside = [body, *_called_from(comps, body)]
    assert not [line for name in inside for line in comps[name] if drawn.search(line)]
    # The draw is in the program all the same, in front of the loop.
    assert [line for line in text.splitlines() if drawn.search(line)]


@pytest.mark.parametrize(
    "name,before,ceiling",
    [
        # a trip of four unrolled updates: with a sum a leaf added up as
        # scalars (the parent's norm) / the ceiling now (PR 46 read 229 ->
        # 189 and 107 -> 87: PERF.md §6)
        ("sac-humanoid", 229, 195),
        ("redq-humanoid", 107, 92),
    ],
)
def test_v5e_scan_body_issues_fewer_scalar_instructions_with_one_sum_a_norm(v5e_sharding, monkeypatch, name, before, ceiling):
    """The TPU's compiler fuses no arithmetic on scalars: in the scan's body
    each is an instruction of the loop by itself (the table's `scalars`,
    trace.chunk_ops_table), and each scalar a fusion hands the scalar core
    costs the loop a wait. learner.optree_norm hands it one a norm where a
    sum a leaf handed it six; a later PR that puts a chain of scalar
    arithmetic behind every leaf learns so here. The configuration's own
    widths, K = 8 (two trips)."""
    from distributed_ddpg_tpu import learner as learner_lib
    from distributed_ddpg_tpu import trace

    def table():
        return trace.chunk_ops_table(_v5e_scan_chunk(v5e_sharding, name, 8)[2].as_text())

    ours = table()
    monkeypatch.setattr(
        learner_lib, "optree_norm",
        lambda tree: jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree))),
    )
    parents = table()
    assert len(ours["loops"]) == len(parents["loops"]) == 1
    assert isinstance(ours["scalars"], int)
    assert parents["scalars"] >= before - 10  # the body as it was
    assert ours["scalars"] <= ceiling
    assert parents["scalars"] - ours["scalars"] >= 20


def test_v5e_redq_chunk_draws_before_the_loop_and_holds_the_policy_under_a_conditional(v5e_sharding):
    """`redq-humanoid`'s scan chunk at the configuration's own sizes (ten
    critics, batch 256, obs 376, act 17, K 800, unroll 4), compiled for the
    described v5e: the chip's compiler takes it; the subset's draw (a
    shuffle's sort and threefry) sits in front of the loop with the normals,
    none of it in the while body; and the policy's half is a conditional in
    the body, not a select over both branches' results."""
    cfg, state, compiled = _v5e_scan_chunk(v5e_sharding, "redq-humanoid", 800)
    assert (cfg.batch_size, cfg.critic_ensemble, cfg.target_subset, cfg.policy_delay) == (256, 10, 2, 20)
    assert state.critic_params[0]["w"].shape == (10, 376, 256)
    text = compiled.as_text()

    comps = _computations(text)
    whiles = [line for lines in comps.values() for line in lines if re.search(r"\bwhile\(", line)]
    body = re.search(r"body=%?([\w.\-]+)", max(whiles, key=lambda w: len(comps[re.search(r"body=%?([\w.\-]+)", w).group(1)]))).group(1)
    inside = [body, *_called_from(comps, body)]
    drawn = re.compile(r'op_name="[^"]*(threefry|_normal|shuffle|choice)')
    assert not [line for name in inside for line in comps[name] if drawn.search(line)]
    assert not [line for line in comps[body] if re.search(r"\b(xor|shift-left|sort)\(", line)]
    assert [line for line in text.splitlines() if drawn.search(line)]
    conditionals = [line for name in inside for line in comps[name] if re.search(r"\bconditional\(", line)]
    assert len(conditionals) == 4  # one an update, four unrolled updates a trip


def test_v5e_crossq_chunk_holds_no_target_update_and_the_policy_under_a_conditional(v5e_sharding):
    """`crossq-humanoid`'s scan chunk at the configuration's own sizes (twin
    2x2048 critics on the joint 512-row batch, actor 2x256, obs 376, act 17,
    K 800, unroll 4), compiled for the described v5e: the chip's compiler
    takes it; 10.2 M parameters with both Adam moments are 123 MB of state
    and no target doubles them; no instruction was traced under `polyak`;
    the batch norm's instructions read `update/critic/norm` and
    `update/actor/norm`; the noise is drawn in front of the loop; and the
    policy's half is a conditional in the body."""
    from distributed_ddpg_tpu import trace

    cfg, state, compiled = _v5e_scan_chunk(v5e_sharding, "crossq-humanoid", 800)
    assert (cfg.crossq, cfg.batch_size, cfg.critic_hidden, cfg.policy_delay, cfg.adam_b1) == (
        True, 256, (2048, 2048), 3, 0.5)
    assert state.target_critic_params is None and state.critic_params[1]["w"].shape == (2, 2048, 2048)
    # the donated state comes back in place: parameters and both moments, three
    # copies of 10.2 M values and no fourth
    assert 120e6 < compiled.memory_analysis().alias_size_in_bytes < 126e6
    text = compiled.as_text()

    scopes = set(trace.chunk_ops_table(text)["ops"].values())
    assert {"update/critic/norm", "update/actor/norm", "update/optim"} <= scopes
    assert "update/polyak" not in scopes and "polyak" not in text
    comps = _computations(text)
    whiles = [line for lines in comps.values() for line in lines if re.search(r"\bwhile\(", line)]
    body = re.search(r"body=%?([\w.\-]+)", max(whiles, key=lambda w: len(comps[re.search(r"body=%?([\w.\-]+)", w).group(1)]))).group(1)
    inside = [body, *_called_from(comps, body)]
    drawn = re.compile(r'op_name="[^"]*(threefry|_normal)')
    assert not [line for name in inside for line in comps[name] if drawn.search(line)]
    assert [line for line in text.splitlines() if drawn.search(line)]
    conditionals = [line for name in inside for line in comps[name] if re.search(r"\bconditional\(", line)]
    assert len(conditionals) == 4  # one an update, four unrolled updates a trip


# --- the scan leg's front (ops/chunk_front.py), compiled for the same
# described v5e inside the chunk it feeds: the gathered block has one
# reader, the scan takes the kernel's outputs as they are, and Mosaic
# compiles the kernel under its default scoped VMEM (nothing is passed). ---


# --- the categorical projection (ops/losses.py), compiled for the same
# described v5e at the DMPO cell's [256, 51]: its one-hots are comparisons
# inside two loop fusions. Indexed out of a 51 x 51 table they were, to this
# compiler, two `kCustom` fusions of a gather of 13,056 rows, each with a
# relayout of the pred[13056, 51] block behind it (PERF.md §6, PR 52). ---


def test_v5e_categorical_projection_gathers_nothing_and_relays_no_one_hot_block(v5e_sharding):
    from distributed_ddpg_tpu import trace
    from distributed_ddpg_tpu.ops import losses

    rows, atoms = 256, 51
    replicated = NamedSharding(v5e_sharding.mesh, P())
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.float32, sharding=replicated)
    compiled = jax.jit(losses.categorical_projection).lower(shape(atoms), shape(rows, atoms), shape(rows), shape(rows)).compile()
    text = compiled.as_text()
    assert " gather(" not in text
    ran = {opcode for _, opcode, _, _ in trace._instructions(text)[0]}
    assert not {"gather", "scatter", "convolution", "dot", "while"} & ran
    # no operation hands another a one-hot block: the [256, 51, 51] masks live inside the fusions that sum them
    block = re.compile(rf"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[(?:{rows * atoms},{atoms}|{rows},{atoms},{atoms})\]")
    assert not [line for line in text.split("ENTRY ", 1)[1].splitlines() if block.search(line)]
    assert compiled.cost_analysis()["bytes accessed"] < 2e6  # [256, 51] operands: 0.84 MB (48.7 MB with the gathers)


def test_v5e_dmpo_chunk_gathers_nothing_in_its_body(v5e_sharding):
    """`dmpo-humanoid`'s scan chunk at the configuration's own sizes (K 800,
    unroll 4), compiled for the described v5e: a trip of its loop issues no
    gather (8 with the projection's one-hots indexed out of a table, two an
    unrolled update) and half the relayouts (69 -> 33 a trip of this chunk,
    which unpacks its batch in XLA: the gathers' own pred[13056, 51] copies
    and reshapes, and the [256, 51] copies round them)."""
    from distributed_ddpg_tpu import trace

    cfg, _, compiled = _v5e_scan_chunk(v5e_sharding, "dmpo-humanoid", 800)
    assert cfg.mpo and cfg.distributional and (cfg.batch_size, cfg.num_atoms) == (256, 51)
    table = trace.chunk_ops_table(compiled.as_text())
    assert len(table["loops"]) == 1
    assert table["gathers"] == 0
    assert table["copies"]["count"] <= 40


@pytest.mark.parametrize(
    "name,extra,chunk,capacity,rounded",
    [
        ("sac-humanoid", [], 800, 1_400_000, True),
        # the cell's mix brings the device pool's flags (traffic/devactors.json)
        ("pql-isaac-humanoid", ["--actor_backend=device", "--num_actors=0"], 96, 5_000_000, True),
        # batch statistics read the observations in float32: nothing is rounded
        ("crossq-humanoid", [], 800, 1_400_000, False),
    ],
)
def test_v5e_scan_chunk_reads_its_gathered_block_once(v5e_sharding, name, extra, chunk, capacity, rounded):
    """The cell's chunk at its own sizes with `storage[idx]` and the cut
    kernel in front, the ring in ring_format's layout. With unpack_batch the
    SAC program held four readers of f32[204800,772] (a cut-and-round fusion,
    a slice-reduce fusion, a slice, and their three relayout copies behind
    them): 2.8 GB moved a launch for 0.33 GB of operands (PERF.md PR 42)."""
    import json
    import os

    from distributed_ddpg_tpu import learner as learner_lib
    from distributed_ddpg_tpu import trace
    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.ops import chunk_front
    from distributed_ddpg_tpu.parallel.learner import scan_chunk

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    conf = json.load(open(os.path.join(root, "benchmarks", "configs", name + ".json")))
    cfg = DDPGConfig.from_flags(conf["flags"] + extra)
    env = conf["env"]
    obs, act = env["obs_dim"], env["act_dim"]
    width, batch = 2 * obs + act + 3, cfg.batch_size
    assert cfg.replay_capacity == capacity and chunk_front.rounds_inputs(cfg) == rounded
    assert chunk_front.front_for(
        width=width, batch=batch, layout=ring_layout(width), replay_sharded=False, model_axis=1, native=True,
    ) == "cut"
    step = learner_lib.make_learner_step(cfg, env["action_scale"], action_offset=env["action_offset"])

    def run(s, storage, idx):
        noise = None
        if learner_lib.draws_noise(cfg):
            noise = learner_lib.chunk_noise(cfg, learner_lib.noise_base_key(cfg), s.step, chunk, batch, act)
        batches = chunk_front.cut_rows(storage[idx], obs, act, rounded, interpret=False)
        return scan_chunk(step, s, batches, noise, unroll=4)

    replicated = NamedSharding(v5e_sharding.mesh, P())
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
        jax.eval_shape(lambda: learner_lib.init_train_state(cfg, obs, act, 0)),
    )
    ring = jax.ShapeDtypeStruct((capacity, width), jnp.float32, sharding=ring_format(v5e_sharding, width))
    idx = jax.ShapeDtypeStruct((chunk, batch), jnp.int32, sharding=replicated)
    compiled = jax.jit(run, donate_argnums=(0,)).lower(state, ring, idx).compile()  # raises what Mosaic would
    text = compiled.as_text()

    entry = [line for line in text.split("ENTRY ", 1)[1].splitlines()[1:] if " = " in line]
    rows = chunk * batch
    block = re.compile(r"f32\[(%d,%d|%d,%d,%d)\]" % (rows, width, chunk, batch, width))
    made = [line for line in entry if re.match(r"\s*%?[\w.\-]+ = " + block.pattern, line)]
    # the gather's fusion and nothing else makes the block (a bitcast of it apart)
    assert len([line for line in made if " bitcast(" not in line]) == 1 and " fusion(" in made[0]
    names = {re.match(r"\s*%?([\w.\-]+) = ", line).group(1) for line in made}
    readers = [
        line for line in entry
        if any(re.search(r"[(, ]%?" + re.escape(n) + r"[,)]", line.split(" = ", 1)[1]) for n in names)
        and " bitcast(" not in line
    ]
    assert len(readers) == 1 and "tpu_custom_call" in readers[0], readers
    # no relayout copy of the observations, the next observations or the action
    fields = re.compile(r"(bf16|f32)\[%d,(%d,(%d|%d)|(%d|%d),%d)\]" % (chunk, batch, obs, act, obs, act, batch))
    assert not [line for line in entry if re.search(r" = " + fields.pattern + r"\{[^}]*\} (copy|transpose)\(", line)]
    assert ring_sized_copies(text, (capacity, width)) == []
    # the custom call reads under `cut`, so chunk.unscoped_pct cannot jump
    table = trace.chunk_ops_table(text)
    call = re.match(r"\s*%?([\w.\-]+) = ", readers[0]).group(1)
    assert table["ops"][call] == "cut"
    # the launch's temporaries: the block, the kernel's outputs, the noise
    outs = rows * (2 * obs + act) * (2 if rounded else 4) + rows * 3 * 4
    assert compiled.memory_analysis().temp_size_in_bytes < rows * 4 * (-(-width // 128) * 128) + 2 * outs + 2**28


# --- the pixel cell's image path (ops/pixels.py), compiled for the same
# described v5e: the crop kernel at the cell's shapes, and the whole chunk
# with the ring in front of it. ---


def _pixel_cell():
    import json
    import os

    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.types import ObsSpec

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    conf = json.load(open(os.path.join(root, "benchmarks", "configs", "drqv2-humanoid.json")))
    cfg = DDPGConfig.from_flags(conf["flags"] + ["--actor_backend=device", "--num_actors=0"])
    env = conf["env"]
    return cfg, env, ObsSpec(tuple(env["obs_shape"]), env["obs_dtype"])


def test_v5e_pixel_crop_compiles_at_the_cells_shapes(v5e_sharding):
    """An update's 256 rows of 15,876 words (batch-minor, as
    ops/pixels.cut_pixels lays a launch) to the encoder's f32[256, 9, 84,
    84] with the kernel native: Mosaic takes the strided stores, the
    unaligned sublane windows and the per-lane shifts, and the program holds
    the kernel once, no loop and no byte-wide array beside it."""
    from distributed_ddpg_tpu.tools import kernel_bundles as kb

    cfg, env, obs = _pixel_cell()
    compiled = kb.lower_crop(cfg, env, NamedSharding(v5e_sharding.mesh, P())).compile()
    text = compiled.as_text()
    assert len(re.findall(r" custom-call\(.*custom_call_target=\"tpu_custom_call\"", text)) == 1
    assert not re.search(r"\bwhile\(", text) and not re.search(r" = [us]8\[", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * cfg.batch_size * obs.size * 4


@pytest.fixture(scope="module")
def v5e_pixel_chunk(v5e_sharding):
    """`drqv2-humanoid`'s launch at its own sizes (the 65,536-row ring in
    ring_format's layout, 32 x 256 indices, unroll 4) as ShardedLearner's
    sample chunk builds it: gather, ops/pixels.cut_pixels, scan_chunk;
    compiled once for the tests below: (cfg, obs, act, width, compiled)."""
    from distributed_ddpg_tpu import learner as learner_lib
    from distributed_ddpg_tpu.ops import pixels as pix
    from distributed_ddpg_tpu.parallel.learner import scan_chunk
    from distributed_ddpg_tpu.types import packed_width

    cfg, env, obs = _pixel_cell()
    act, chunk, batch = env["act_dim"], cfg.learner_chunk, cfg.batch_size
    width = packed_width(obs, act)
    assert (chunk, batch, width, cfg.replay_capacity) == (32, 256, 31776, 65536)
    replicated = NamedSharding(v5e_sharding.mesh, P())
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
        jax.eval_shape(lambda: learner_lib.init_train_state(cfg, obs, act, 0)),
    )
    ring = jax.ShapeDtypeStruct((cfg.replay_capacity, width), jnp.float32, sharding=ring_format(v5e_sharding, width))
    idx = jax.ShapeDtypeStruct((chunk, batch), jnp.int32, sharding=replicated)
    nkey = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=replicated)
    with pytest.MonkeyPatch.context() as patch:
        # the process runs on the CPU, where the kernel would be interpreted: compile it
        patch.setattr(pix, "random_shift", functools.partial(pix.random_shift, interpret=False))
        step = learner_lib.make_learner_step(cfg, env["action_scale"], action_offset=env["action_offset"], obs=obs)

        def run(s, storage, idx, nkey):
            noise = learner_lib.chunk_noise(cfg, nkey, s.step, chunk, batch, act)
            return scan_chunk(step, s, pix.cut_pixels(storage[idx], obs, act), noise, unroll=4)

        compiled = jax.jit(run, donate_argnums=(0,)).lower(state, ring, idx, nkey).compile()
    return cfg, obs, act, width, compiled


def _scan_body(text):
    """(the lines of the one `while`'s body, {computation: lines})."""
    comps = _computations(text)
    whiles = [line for lines in comps.values() for line in lines if re.search(r"\bwhile\(", line)]
    assert len(whiles) == 1
    return comps[re.search(r"body=%?([\w.\-]+)", whiles[0]).group(1)], comps


def test_v5e_pixel_chunk_holds_the_scans_loop_alone_and_no_expanded_bytes(v5e_pixel_chunk):
    """With the byte images cut in front of the scan and a vmapped
    dynamic_slice an update (PR 47) the text held nine `while`s, the scan's
    and eight crop loops of 256 trips, and `u32[32,256,15876,4]`, 32 bits a
    pixel of the launch's block, twice (PERF.md, PR 48)."""
    cfg, obs, act, width, compiled = v5e_pixel_chunk
    chunk, batch = cfg.learner_chunk, cfg.batch_size
    text = compiled.as_text()
    body, _ = _scan_body(text)
    # two images an update, four unrolled updates a trip
    kernels = [line for line in body if "tpu_custom_call" in line]
    assert len(kernels) == 8 and all("augment/pixel_crop" in line for line in kernels)
    # no array holds an update's images as bytes (XLA's own byte masks are smaller), none is larger than the launch's cut words
    big = chunk * batch * obs.words
    shaped = re.compile(r" = (\w+)\[([\d,]+)\]")
    for line in text.splitlines():
        m = shaped.search(line)
        size = np.prod([int(d) for d in m.group(2).split(",")]) if m else 0
        if m and m.group(1) in ("u8", "s8"):
            assert size < batch * obs.size, line
        if size > big:
            assert " parameter(" in line or "gather" in line or " bitcast(" in line, line
    assert ring_sized_copies(text, (cfg.replay_capacity, width)) == []
    # the launch's temporaries: the gathered block, the two cut fields' relayout, the noise
    assert compiled.memory_analysis().temp_size_in_bytes < 2.2 * chunk * batch * width * 4


def test_v5e_pixel_chunk_moves_no_feature_block_between_the_encoder_and_the_trunks(v5e_pixel_chunk):
    """The encoder's features reach the five trunk products an update, and
    the block's gradient the last convolution's backward, as the
    convolutions lay them (`[256,32,35,35]{0,1,3,2}`: batch-minor, channels
    next; PR 50): the loop's body holds no `copy`, `transpose`, `reshape` or
    relayout fusion whose operand or result is a feature block, in either
    type, nor one of a trunk's weight, whose rows a launch moves to the
    block's order once in front of the scan (learner.pixel_step.launch).
    Flattened channel-major for `features @ w` the body held, a trip of four
    updates, eight `copy` of `bf16[256,32,35,35]` to `{0,3,2,1}`, eight
    `reshape` to `bf16[256,39200]` and four `reshape` of the gradient back:
    7.5 ms of a 102.7 ms launch on the chip (PERF.md §6, PR 50). What
    `chunk_ops_table` counts as `copies` is what is left, counted here by
    hand: the crop's eight retiles of an update's words."""
    from distributed_ddpg_tpu import trace
    from distributed_ddpg_tpu.models import pixels as pixnet

    cfg, obs, act, width, compiled = v5e_pixel_chunk
    batch, c, f = cfg.batch_size, cfg.encoder_channels, cfg.feature_dim
    side = pixnet.feature_side(obs.shape[-1])
    assert (batch, c, side, f) == (256, 32, 35, 100)
    text = compiled.as_text()
    body, comps = _scan_body(text)
    held = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\((.*)$")
    shapes = {m.group(1): m.group(2) for lines in comps.values() for m in map(held.match, lines) if m}
    dims = lambda shape: tuple(int(d) for d in re.search(r"\[([\d,]*)\]", shape).group(1).split(",") if d)
    blocks = {(batch, c, side, side), (batch, side, side, c), (batch, c * side * side), (batch, c, side * side), (batch, side * side, c)}
    weights = {(c * side * side, f), (c, side, side, f), (side, side, c, f), (c, side * side, f), (side * side, c, f)}
    moves, retiles = [], 0
    for m in filter(None, map(held.match, body)):
        name, shape, opcode, rest = m.groups()
        callee = re.search(r"calls=%?([\w.\-]+)", rest)
        inside = {held.match(line).group(3) for line in comps[callee.group(1)]} if opcode == "fusion" else {opcode}
        if not inside <= {"parameter", "bitcast", "copy", "transpose", "reshape"} or not inside & {"copy", "transpose", "reshape"}:
            continue
        operands = [shapes[o] for o in re.findall(r"%([\w.\-]+)", rest.split("), ")[0]) if o in shapes]
        touched = {dims(x) for x in (shape, *operands) if "[" in x}
        moves.append((name, shape, touched))
        retiles += dims(shape) == (obs.shape[0], obs.shape[1], obs.shape[2] // 4, batch)
    assert not [move for move in moves if move[2] & (blocks | weights)], moves
    # an update's two images' words, four unrolled updates a trip: s32[9,84,21,256]
    assert retiles == len(moves) == 8
    assert trace.chunk_ops_table(text)["copies"] == {"count": 8, "bytes": 8 * obs.words * batch * 4}


def test_v5e_recurrent_chunk_scans_time_inside_the_scan_over_updates(v5e_sharding):
    """`rtd3-isaac-humanoid-p`'s launch at its own sizes (the 245,760-row ring
    of 5,046-float windows in ring_format's layout, 128 x 64 indices, unroll
    4) as ShardedLearner's sample chunk builds it: gather, types.
    unpack_windows, scan_chunk. The TPU's compiler takes it; the body of the
    scan over updates holds, an update, six loops over the window's steps (the
    two targets' memories forward, the critic's and the actor's forward and
    back through time), every one under a `recur` scope that the program's
    table reads through `jvp` and `transpose`; what the launch holds beside
    the ring (its 8,192 gathered windows, 165 MB, cut and relaid, and the
    noise) stays under an eighth of it."""
    import json
    import os

    from distributed_ddpg_tpu import learner as learner_lib
    from distributed_ddpg_tpu import trace
    from distributed_ddpg_tpu.config import DDPGConfig
    from distributed_ddpg_tpu.parallel.learner import scan_chunk
    from distributed_ddpg_tpu.types import ObsSpec, packed_width, unpack_windows

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    conf = json.load(open(os.path.join(root, "benchmarks", "configs", "rtd3-isaac-humanoid-p.json")))
    cfg = DDPGConfig.from_flags(conf["flags"] + ["--actor_backend=device", "--num_actors=0"])
    env = conf["env"]
    obs = ObsSpec((env["obs_dim"],), steps=cfg.window_steps)
    act, chunk, batch, unroll = env["act_dim"], cfg.learner_chunk, cfg.batch_size, 4
    width = packed_width(obs, act)
    assert (chunk, batch, width, cfg.replay_capacity) == (128, 64, 5046, 245760)
    replicated = NamedSharding(v5e_sharding.mesh, P())
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
        jax.eval_shape(lambda: learner_lib.init_train_state(cfg, obs, act, 0)),
    )
    ring = jax.ShapeDtypeStruct((cfg.replay_capacity, width), jnp.float32, sharding=ring_format(v5e_sharding, width))
    idx = jax.ShapeDtypeStruct((chunk, batch), jnp.int32, sharding=replicated)
    nkey = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=replicated)
    step = learner_lib.make_learner_step(cfg, env["action_scale"], action_offset=env["action_offset"], obs=obs)

    def run(s, storage, idx, nkey):
        noise = learner_lib.chunk_noise(cfg, nkey, s.step, chunk, batch, act)
        return scan_chunk(step, s, unpack_windows(storage[idx], obs.words, act, obs.steps), noise, unroll=unroll)

    compiled = jax.jit(run, donate_argnums=(0,)).lower(state, ring, idx, nkey).compile()
    text = compiled.as_text()
    scopes = trace.op_scopes(text)
    loops = [name for name in scopes if name.startswith("while")]
    by_scope = {s: sum(scopes[name] == s for name in loops) for s in set(scopes[name] for name in loops)}
    assert by_scope == {
        "update": 1, "update/target/recur": 2 * unroll, "update/critic/recur": 2 * unroll,
        "update/actor/recur": 2 * unroll,
    }, by_scope
    ring_bytes = cfg.replay_capacity * 5120 * 4  # 40 lines of 128 words a row
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes > ring_bytes and memory.temp_size_in_bytes < ring_bytes // 8
