"""Deliberately-broken jitted programs for the program-contract analyzer
(analysis/programs.py; docs/ANALYSIS.md "Layer 2").

Each registry below is a tiny `program_specs()`-shaped callable the
proganalyze CLI can load via `--specs tests/program_fixtures.py:<name>`
and tests/test_programs.py drives in-process. One registry per failure
mode, so each broken program independently proves its check fires with
an exact finding count:

- `broken_donation_specs`   — a donated buffer whose shape/dtype matches
                              no output: lowering records NO aliasing
                              for it (the silent 2x HBM class).
- `broken_callback_specs`   — a `pure_callback` embedded in the jitted
                              program (the host-round-trip-per-beat
                              class).
- `collective_specs_v1/_v2` — the SAME program name tracing psum->pmax
                              vs pmax->psum: golden one, check the
                              other, and the collective-order gate must
                              fire (the pod-fork/exit-76 class).
- `broken_beat_group_specs` — two variants claiming one beat_group with
                              different collective orders.
- `clean_specs`             — a well-formed donating + collective
                              program for golden roundtrip tests.
- `seed_constant_specs`     — one program that traces PRNGKey(config.seed)
                              into its body and one that takes the same
                              key as an argument: the seed-constant rule
                              (analysis/progrules.py) must flag the first
                              and only the first.

These run under the same probe mesh as the live registries; everything
is traced/lowered only — nothing here ever executes.
"""

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from distributed_ddpg_tpu.analysis.programs import (
    BuiltProgram,
    ProgramSpec,
    probe_config,
    probe_mesh,
)
from distributed_ddpg_tpu.parallel.mesh import shard_map

OWNER = "tests/program_fixtures.py"


# -- unaliased donation -----------------------------------------------------


def _unaliased_donation() -> BuiltProgram:
    # buf is donated but (7,) f32 matches no output (the only output is
    # (3,) f32): XLA cannot alias it, the donation silently buys nothing.
    fn = jax.jit(lambda buf, x: x * 2.0, donate_argnums=(0,))
    return BuiltProgram(
        fn, (np.zeros(7, np.float32), np.zeros(3, np.float32)), (0,)
    )


def broken_donation_specs():
    return [
        ProgramSpec("fixture.donation.unaliased", OWNER, _unaliased_donation)
    ]


# -- host-callback leak -----------------------------------------------------


def _callback_leak() -> BuiltProgram:
    def fn(x):
        y = x + 1.0
        return jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct(x.shape, x.dtype), y
        )

    return BuiltProgram(jax.jit(fn), (np.zeros(4, np.float32),))


def broken_callback_specs():
    return [ProgramSpec("fixture.callback.leak", OWNER, _callback_leak)]


# -- collective order -------------------------------------------------------


def _collective_pair(order: str):
    def build() -> BuiltProgram:
        mesh = probe_mesh()

        def body(x):
            if order == "sum-first":
                s = jax.lax.psum(x, "data")
                m = jax.lax.pmax(x, "data")
            else:
                m = jax.lax.pmax(x, "data")
                s = jax.lax.psum(x, "data")
            return s + m

        fn = jax.jit(
            shard_map(body, mesh, in_specs=P("data"), out_specs=P("data"))
        )
        return BuiltProgram(fn, (np.zeros(8, np.float32),))

    return build


def collective_specs_v1():
    return [
        ProgramSpec(
            "fixture.collective.pair", OWNER, _collective_pair("sum-first")
        )
    ]


def collective_specs_v2():
    # Same name, opposite order: checked against v1's golden this is the
    # reorder that forks a pod's device-op streams.
    return [
        ProgramSpec(
            "fixture.collective.pair", OWNER, _collective_pair("max-first")
        )
    ]


# -- beat-group divergence --------------------------------------------------


def broken_beat_group_specs():
    return [
        ProgramSpec(
            "fixture.beat.a", OWNER, _collective_pair("sum-first"),
            beat_group="fixture-beat",
        ),
        ProgramSpec(
            "fixture.beat.b", OWNER, _collective_pair("max-first"),
            beat_group="fixture-beat",
        ),
    ]


# -- clean program (roundtrip oracle) ---------------------------------------


def _clean_program() -> BuiltProgram:
    mesh = probe_mesh()

    def body(acc, x):
        return acc + jax.lax.psum(x, "data")

    fn = jax.jit(
        shard_map(body, mesh, in_specs=(P("data"), P("data")),
                  out_specs=P("data")),
        donate_argnums=(0,),
    )
    return BuiltProgram(
        fn, (np.zeros(8, np.float32), np.zeros(8, np.float32)), (0,)
    )


def clean_specs():
    return [ProgramSpec("fixture.clean", OWNER, _clean_program)]


# -- a seed traced into the program as a constant --------------------------


def _seed_in_the_text() -> BuiltProgram:
    seed = probe_config().seed
    fn = jax.jit(
        lambda x: x + jax.random.normal(jax.random.PRNGKey(seed ^ 0x5AC0), x.shape)
    )
    return BuiltProgram(fn, (np.zeros(3, np.float32),))


def _seed_as_an_argument() -> BuiltProgram:
    key = jax.random.PRNGKey(probe_config().seed ^ 0x5AC0)
    fn = jax.jit(lambda x, k: x + jax.random.normal(k, x.shape))
    return BuiltProgram(fn, (np.zeros(3, np.float32), key))


def seed_constant_specs():
    return [
        ProgramSpec("fixture.seed.constant", OWNER, _seed_in_the_text),
        ProgramSpec("fixture.seed.argument", OWNER, _seed_as_an_argument),
    ]
