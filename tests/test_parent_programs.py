"""The programs of the nine configurations the benchmark had before the pixel
one lower to the text they lowered to at its parent commit (ace7f00, PR 46;
jax 0.9.0): `tests/lowered_text_pr46.json` holds sha256[:16] of
`fn.lower(*args).as_text()` of every program of the registry
(analysis/programs.default_specs), taken there on the 8 virtual CPU devices.
A change that routes a flat configuration's widths through types.ObsSpec, adds
a branch to the step, a word to the scopes or a leaf to the carry must leave
these texts alone: the compile cache keys on them, and the chip's programs
are compiled from them.

One case a program the nine cells launch or share with the pixel cell (the
sampling chunks of each family, the host-fed chunk, the ring inserts of both
layouts, the rollouts, the serving apply); the file holds the rest of the
registry too (the guarded, sharded and fused variants, which trace the same
bodies), and `python -m distributed_ddpg_tpu.tools.proganalyze` holds their
collective order.

A later PR that MEANS to change one of these programs takes the file anew:
in a checkout of its own parent, under the conftest's settings (JAX on the
CPU, `--xla_force_host_platform_device_count=8`,
`jax_threefry_partitionable`), `{spec.name: sha256(build().fn.lower(*args)
.as_text())[:16] for spec in default_specs()}` as JSON, and names the
programs it moved in HELD's place.
"""

import hashlib
import json
import pathlib

import pytest

from distributed_ddpg_tpu.analysis.programs import default_specs

AT_PARENT = json.loads(pathlib.Path(__file__).with_name("lowered_text_pr46.json").read_text())
HELD = [
    "learner.chunk.uniform", "learner.chunk.per", "learner.chunk.hostfed", "learner.chunk.uniform.ensemble",
    "learner.chunk.uniform.crossq", "learner.chunk.uniform.pql", "learner.chunk.uniform.simba",
    "learner.chunk.uniform.sharded", "replay.insert", "replay.insert.packed", "replay.stamp",
    "devactor.rollout", "devactor.rollout.nstep", "serve.apply.jax",
]


# The three programs the PR 46 file lacks (registered by PRs 47 and 51), as they
# lowered at PR 53's parent (5063af3, PR 52), taken the same way: the pixel and
# the DMPO cell's chunks and the pixel rollout, which share types.ObsSpec,
# learner.step_noise and ActorCarry with what PR 53 changed.
AT_PR52 = {
    "learner.chunk.uniform.pixels": "21b8922d38222303",
    "learner.chunk.uniform.mpo": "cab64179c1e55c51",
    "devactor.rollout.pixels": "be7f06c488b056d8",
}


@pytest.fixture(scope="module")
def specs():
    return {spec.name: spec for spec in default_specs()}


def test_the_file_names_the_parents_whole_registry(specs):
    assert set(HELD) <= set(AT_PARENT) and len(AT_PARENT) == 50
    # what this PR registered beside them, and nothing it took away
    assert set(specs) - set(AT_PARENT) == {
        "learner.chunk.uniform.pixels", "devactor.rollout.pixels",  # PR 47
        "learner.chunk.uniform.mpo",  # PR 51: DMPO's chunk; the texts below stand with it registered
        # PR 53: recurrent TD3's chunk and rollout (rows that are windows, a policy state in the carry);
        # the texts below stand with them registered, ObsSpec.steps and ActorCarry's new leaves included
        "learner.chunk.uniform.recurrent", "devactor.rollout.recurrent",
    }
    assert set(AT_PARENT) <= set(specs)


@pytest.mark.parametrize("name", HELD + sorted(AT_PR52))
def test_program_lowers_to_the_parents_text(specs, name):
    built = specs[name].build()
    text = built.fn.lower(*built.args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == {**AT_PARENT, **AT_PR52}[name]
