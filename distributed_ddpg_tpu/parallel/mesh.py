"""Device mesh + sharding specs (replaces the reference's parameter-server
variable placement, SURVEY.md §2 #10 / §5 'Distributed communication backend').

The reference pins variables to /job:ps and replicates worker graphs over
gRPC. Here the topology is a `jax.sharding.Mesh` with two named axes:

- `data`: the data-parallel axis. Replay minibatches shard their leading
  (batch) dim here; XLA turns the per-shard gradient contributions into one
  AllReduce over ICI (the `psum` the north star names, BASELINE.json:5).
  Sharded device replay partitions its HBM ring over this axis too
  (docs/REPLAY_SHARDING.md).
- `model`: tensor parallelism. Params shard over this axis according to
  the regex partition-rule tables in `parallel/partition.py` (Megatron
  column-/row-parallel alternation by default; per-net tables for
  anything else — docs/MESH.md has the grammar and the add-a-rule
  recipe). model_axis > 1 composes with sharded replay, device actors,
  the serve jax backend, and the fused megastep: per-device param +
  optimizer HBM divides by the model-axis size.

This module owns the MESH (make_mesh, shard_map, to_named) and the
batch-side specs; the param-side spec construction (net_pspec,
state_pspec) lives in partition.py and is re-exported here so existing
callers keep their import path.

Multi-host (DCN) uses the SAME mesh/specs: jax.distributed.initialize makes
jax.devices() span hosts, and XLA routes the collective hierarchically
(ICI within host, DCN across; SURVEY.md §5 row 'Distributed comm backend').
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_ddpg_tpu.parallel.partition import (  # noqa: F401 (re-export)
    PartitionRuleError,
    match_partition_rules,
    mlp_rules,
    net_pspec,
    state_pspec,
)
from distributed_ddpg_tpu.types import Batch

# Placement-invariant PRNG (the future jax default): with the legacy
# non-partitionable threefry, the VALUES jax.random produces inside a
# jitted program depend on the mesh's model-axis size — measured: the
# same key draws different normals under (4, 1) vs (4, 2) meshes — which
# would make every sampled minibatch and OU-noise stream a function of
# the TP degree and break the model_axis parity oracle
# (tests/test_partition.py). Set at import of THIS module — every
# device-program owner imports it before building programs, so all
# programs in a process trace under one consistent scheme regardless of
# which entry point (train/benchmark/proganalyze/multihost child) started
# it. An explicit JAX_THREEFRY_PARTITIONABLE in the environment wins:
# that is the embedder's escape hatch back to the legacy scheme.
import os as _os
import pathlib as _pathlib

if _os.environ.get("JAX_THREEFRY_PARTITIONABLE", "") == "":
    jax.config.update("jax_threefry_partitionable", True)

# Persistent compile cache, placed from outside. One rule, here, next to
# the other process-wide JAX setting every device-program owner inherits
# by importing this module: when JAX_COMPILATION_CACHE_DIR is set the
# code assigns nothing and JAX uses that directory; otherwise the cache
# is <checkout>/.jax_cache, derived from this package's own path (the
# directory is part of what makes an entry hit, so it must not move
# between processes or working directories of one checkout). A process
# that asked for the CPU gets no default: the cache is there for the
# chip's compiles, and this installation's XLA:CPU loader logs a
# machine-feature error on every hit and would trust an entry carried
# over from another host.
if (
    not _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    and (jax.config.jax_platforms or "").split(",")[0] != "cpu"
):
    jax.config.update(
        "jax_compilation_cache_dir",
        str(_pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"),
    )
# The key takes the programs' metadata, wherever the cache lies. JAX leaves
# it out by default, so a program that differs from a cached one in its
# `jax.named_scope` brackets alone loads the other's executable, and the
# text of that executable carries the other's `op_name`s: the table the
# learner writes from it (ShardedLearner.chunk_ops) would describe a
# program that was never traced here. With it, an entry only ever answers
# the source it was compiled from (file and line are metadata too).
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def shard_map(f, mesh: Mesh, in_specs, out_specs, check: bool = False):
    """Per-shard body with explicit collectives; specs name this module's
    (data, model) axes. `check` is jax.shard_map's check_vma."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )


def make_mesh(
    data_axis: int = -1,
    model_axis: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a (data, model) mesh. data_axis=-1 means 'all remaining devices'."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if model_axis < 1 or n % model_axis:
        raise ValueError(f"model_axis={model_axis} must divide device count {n}")
    if data_axis == -1:
        data_axis = n // model_axis
    if data_axis * model_axis != n:
        raise ValueError(
            f"mesh {data_axis}x{model_axis} != {n} devices"
        )
    arr = np.asarray(devices).reshape(data_axis, model_axis)
    return Mesh(arr, ("data", "model"))


def batch_pspec() -> Batch:
    """Minibatches shard their batch dim over 'data' (fields are [B, ...])."""
    return Batch(
        obs=P("data", None),
        action=P("data", None),
        reward=P("data"),
        discount=P("data"),
        next_obs=P("data", None),
        weight=P("data"),
    )


def to_named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
