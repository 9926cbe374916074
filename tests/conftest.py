"""Test harness: force JAX onto a virtual 8-device CPU platform BEFORE any
backend is initialized (SURVEY.md §4 'Distributed without a cluster'). This
exercises the mesh/sharding/collective paths with no TPU attached; the driver
separately dry-runs the multichip path via __graft_entry__.dryrun_multichip.

The platform is pinned through jax.config (not only the env var) so a bare
`pytest tests/` without JAX_PLATFORMS=cpu still never reaches for a chip:
this is the "CPU was asked for" that train.require_platform honours.
"""

import os

import pytest

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Placement-invariant PRNG — the repo-wide RNG scheme (see the note in
# parallel/mesh.py): set here too so tests that touch jax.random before
# importing parallel.mesh trace under the same scheme.
jax.config.update("jax_threefry_partitionable", True)


@pytest.fixture
def one_chip(monkeypatch):
    """train() on the first device alone, the deployment's topology: the
    virtual 8-device mesh would put collectives into the rollout and the chunk
    programs, and XLA:CPU can deadlock two such programs in flight when the
    host's threads are scarce (a rendezvous of 8 that never fills, and the
    process aborts after 40 s; PERF.md §7, 24). For tests that drive train()
    with a device pool and the unfused loop; not autouse."""
    from distributed_ddpg_tpu.parallel import mesh as mesh_lib

    make = mesh_lib.make_mesh
    monkeypatch.setattr(
        mesh_lib, "make_mesh", lambda data_axis=-1, model_axis=1, devices=None: make(1, 1, jax.devices()[:1]))


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """as_on_a_tpu(native=True): the platform input of the scan front's rule
    (ops/chunk_front.front_for) alone, so that a learner builds the program a
    real TPU would get while the kernel itself still runs interpreted here."""
    from distributed_ddpg_tpu.ops import chunk_front

    rule = chunk_front.front_for

    def patch(native=True):
        monkeypatch.setattr(chunk_front, "front_for", lambda **seen: rule(**{**seen, "native": native}))

    return patch

