"""The benchmark's cells against the program, without a chip or a compile.

`BENCHMARK.json`, `benchmarks/configs/*.json` and `benchmarks/traffic/*.json`
are data the driver runs on the TPU after a PR exits; nothing else in tier-1
reads them. So a PR that deletes a flag a cell passes, moves the kernel's
envelope, or changes what a ring row costs would learn it from the driver's
chip run. One case per `workloads` entry: a cell added to the file is
covered by itself (its ring layout has to be entered below). Two cells may
share a configuration's file (`sac-humanoid.free.x4` runs `sac-humanoid`
under a traffic file of its own, on four chips): each is a case, so the
shared file is held twice and the cell's own traffic file once.

Read only: this file edits none of what it reads and imports nothing under
`benchmarks/`.
"""

import json
import pathlib

import pytest

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.ops import fused_chunk
from distributed_ddpg_tpu.replay.device import ring_layout, ring_row_bytes
from distributed_ddpg_tpu.types import ObsSpec, packed_width

ROOT = pathlib.Path(__file__).resolve().parent.parent

# The driver refuses a cell that holds under a quarter of a chip's 16 GiB
# (`cell_too_small`). The nets are a few MB, so the ring is what clears it.
MEMORY_FLOOR_BYTES = 4 * 2**30

# What PERF.md §3 (ingest layer) says of each configuration's ring.
RING_LAYOUT = {
    "ddpg-halfcheetah": "packed",
    "d4pg-halfcheetah": "packed",
    "sac-humanoid": "row_major",
    "td3-halfcheetah": "packed",
    "redq-humanoid": "row_major",
    "crossq-humanoid": "row_major",
    "pql-isaac-humanoid": "row_major",
    "simba-humanoid": "row_major",
    "drqv2-humanoid": "row_major",
    "dmpo-humanoid": "row_major",
    "rtd3-isaac-humanoid-p": "row_major",  # a row is a window: 5,046 floats, 40 lines of 128
}


def _obs(env):
    """A configuration's observation: byte frames where its file names a
    shape and a dtype, the flat vector `obs_dim` counts otherwise."""
    if "obs_shape" in env:
        return ObsSpec(tuple(env["obs_shape"]), env["obs_dtype"])
    return ObsSpec.of(env["obs_dim"])


def _load(*parts):
    return json.loads(ROOT.joinpath(*parts).read_text())


WORKLOADS = {cell["name"]: cell for cell in _load("BENCHMARK.json")["workloads"]}
CELLS = list(WORKLOADS)


def _files(cell_name):
    """(configuration name, its file, the flags the cell hands `train()`)."""
    entry = WORKLOADS[cell_name]
    config = _load("benchmarks", "configs", entry["config"] + ".json")
    traffic = _load("benchmarks", "traffic", entry["traffic"] + ".json")
    # As benchmarks/run.py joins them: the configuration's, then the mix's.
    return entry["config"], config, list(config["flags"]) + list(traffic["flags"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_flags_parse(cell):
    """Every flag a cell passes is still a field, and the values pass the
    cross-field validation `train()` would run them through."""
    _, config, flags = _files(cell)
    cfg = DDPGConfig.from_flags(flags)  # argparse exits, validation raises
    assert cfg.backend == "jax_tpu"
    assert cfg.env_id == config["env"]["id"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_leg_is_what_the_file_expects(cell):
    """The leg the cell's `why` is written around: the megakernel where the
    configuration is inside its envelope and its VMEM budget, the scan chunk
    otherwise (parallel/learner.py's rule, less what only a device says)."""
    _, config, flags = _files(cell)
    cfg = DDPGConfig.from_flags(flags)
    env = config["env"]
    picks_kernel = fused_chunk.supported(cfg) and fused_chunk.fits_vmem(
        cfg, _obs(env).words, env["act_dim"]
    )
    assert picks_kernel == config["expects"]["fused_chunk_active"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_ring_layout_and_size(cell):
    name, config, flags = _files(cell)
    cfg = DDPGConfig.from_flags(flags)
    env = config["env"]
    # a recurrent configuration's row is a window of its seq_len steps
    width = packed_width(_obs(env)._replace(steps=cfg.window_steps), env["act_dim"])
    layout = ring_layout(width)
    assert name in RING_LAYOUT, f"enter {name}'s ring layout ({layout}) in RING_LAYOUT"
    assert layout == RING_LAYOUT[name]
    if "replay_capacity" in config["reduced"]:
        # The ring is what was sized to the floor: a layout change that
        # shrinks a row must not take the cell under it unnoticed.
        ring_bytes = ring_row_bytes(width, layout) * cfg.replay_capacity
        assert ring_bytes > MEMORY_FLOOR_BYTES, (
            f"{name}: ring {ring_bytes / 2**30:.2f} GiB under the driver's 4.00 GiB floor"
        )
