"""Test harness: force JAX onto a virtual 8-device CPU platform BEFORE any
backend is initialized (SURVEY.md §4 'Distributed without a cluster'). This
exercises the mesh/sharding/collective paths with no TPU attached; the driver
separately dry-runs the multichip path via __graft_entry__.dryrun_multichip.

The platform is pinned through jax.config (not only the env var) so a bare
`pytest tests/` without JAX_PLATFORMS=cpu still never reaches for a chip:
this is the "CPU was asked for" that train.require_platform honours.
"""

import os

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
