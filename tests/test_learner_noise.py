"""The scan leg's noise, drawn once a launch before the scan
(learner.chunk_noise; parallel/learner.py draw_chunk_noise).

- the helper's [K, B, act] is `normal(split(fold_in(base, step0 + k)))` row
  by row, written out here with jax.random alone;
- every chunk program of the scan leg (uniform, PER, guarded, explicit
  shard_map on 2 virtual devices, auto on a 2-device mesh) ends where the
  steps that draw for themselves end: TrainState, TD errors and the chunk's
  metrics. To the bit against the chunk as it was before the noise left the
  loop (one scan whose every step draws its own), but for SAC on two
  devices; there, and against K dispatches of the single-step program,
  within the tolerance tests/test_fused_chunk.py holds the two legs to:
  XLA:CPU contracts `mean + std * eps` differently when the draw is fused
  into it, and a scan's body differently from a program of one step (1e-7
  relative; so it did with the draw in the step). The noise itself is equal
  to the bit everywhere (the first test, with and without the device fold);
- a guarded chunk that drops an update stays aligned with the stream;
- the structure: no RNG primitive inside the scan body of a noise-bearing
  chunk program, and DDPG / D4PG scan over their batches alone;
- the stream's base key is an ARGUMENT of every chunk program that draws
  (ShardedLearner._noise_key), so a program lowers to the same text at
  every seed, and the bits a seed gives are the ones a constant key gave;
  a program that draws nothing has no such parameter.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from distributed_ddpg_tpu import guardrails
from distributed_ddpg_tpu import learner as learner_lib
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.parallel import mesh as mesh_lib
from distributed_ddpg_tpu.parallel.learner import ShardedLearner
from distributed_ddpg_tpu.replay.device import (
    DevicePrioritizedReplay,
    DeviceReplay,
    draw_per_indices,
)
from distributed_ddpg_tpu.types import unpack_batch

OBS, ACT, B, K, ROWS = 5, 3, 16, 8, 256
ALGOS = {
    "sac": dict(sac=True),
    "td3": dict(twin_critic=True, target_noise=0.2, policy_delay=2),
}
# the families whose stream has a third member or a state of another shape
FAMILIES = {
    **ALGOS,
    "redq": dict(sac=True, critic_ensemble=5, target_subset=2, policy_delay=3),
    "crossq": dict(
        sac=True, crossq=True, policy_delay=3, adam_b1=0.5,
        action_insert_layer=0,
    ),
}
QUIET = {
    "ddpg": dict(),
    "d4pg": dict(distributional=True, num_atoms=11, v_min=-5.0, v_max=5.0),
    "td3_unsmoothed": dict(twin_critic=True, target_noise=0.0),
}
# variant -> (devices, mode, per, guarded)
VARIANTS = {
    "uniform": (1, "auto", False, False),
    "per": (1, "auto", True, False),
    "guarded": (1, "auto", False, True),
    "per_guarded": (1, "auto", True, True),
    "explicit": (2, "explicit", False, False),
    "auto_mesh2": (2, "auto", False, False),
}
RNG_PRIMITIVES = {
    "random_bits", "threefry2x32", "random_fold_in", "random_split",
    "random_seed",
}


def _cfg(algo, guarded=False, per=False, **kw):
    fields = dict(
        actor_hidden=(16, 16), critic_hidden=(16, 16), batch_size=B, seed=7,
        fused_chunk="off", scale_batch_with_data=False, guardrails=guarded,
        guardrail_warmup_steps=10_000, prioritized=per,
        **{**FAMILIES, **QUIET}[algo],
    )
    return DDPGConfig(**{**fields, **kw})


def _learner(algo, variant, chunk=K, **kw):
    devices, mode, per, guarded = VARIANTS[variant]
    mesh = mesh_lib.make_mesh(devices, 1, devices=jax.devices()[:devices])
    return ShardedLearner(
        _cfg(algo, guarded, per, **kw), OBS, ACT, 1.5, 0.25, mesh=mesh,
        mode=mode, chunk_size=chunk,
    )


def _replay(learner, per):
    cls = DevicePrioritizedReplay if per else DeviceReplay
    rep = cls(1000, OBS, ACT, mesh=learner.mesh, block_size=64)
    rows = np.random.default_rng(0).standard_normal((ROWS, rep.width))
    rows[:, OBS + ACT + 1] = 0.99  # discount
    rows[:, -1] = 1.0              # weight
    rep.add_packed(rows.astype(np.float32))
    rep.drain_pending()
    return rep


def _chunk_rows(learner, rep, per, beta):
    """The [K, B, width] rows the learner's NEXT chunk will draw, by the
    draw its programs make (draw_chunk_idx / per_sample_chunk_fn)."""
    _, sub = jax.random.split(jnp.array(learner._key))
    if per:
        storage, size, priorities, _ = rep.per_state()
        idx, weights = draw_per_indices(
            sub, priorities, size, (K, learner.global_batch), beta
        )
        return np.asarray(storage[idx].at[..., -1].set(weights))
    storage, size = rep.device_state()
    idx = jax.random.randint(
        sub, (K, learner.global_batch), 0, jnp.maximum(size, 1)
    )
    return np.asarray(storage[idx])


def _k_single_steps(ref, rows):
    """K dispatches of the single-step program, each drawing its own noise."""
    tds, ms = [], []
    for k in range(K):
        out = ref._step(
            ref.state, jax.device_put(rows[k], ref._batch_sharding)
        )
        ref.state = out.state
        tds.append(np.asarray(out.td_errors))
        ms.append(jax.device_get(out.metrics))
    # as learner.chunk_metrics: means, but the last update's td3_twin_gap
    metrics = {
        k: ms[-1][k] if k in learner_lib.LAST_UPDATE_KEYS
        else np.mean([m[k] for m in ms])
        for k in ms[0]
    }
    return jax.device_get(ref.state), np.stack(tds), metrics


def _scan_that_draws_in_its_steps(learner):
    """The chunk as it was before the noise left the loop: one scan whose
    every step draws for itself, (state, rows[, guard]) -> (state, TD
    errors, metrics[, guard]); under the health probe where the learner's
    own chunk is, with the learner's injected faults."""
    cfg, mesh = learner.config, learner.mesh
    if learner.mode == "explicit":
        inner = learner_lib.make_learner_step(
            cfg, 1.5, axis_name="data", action_offset=0.25
        )
        spec = mesh_lib.state_pspec(learner.state, mesh)
        step = mesh_lib.shard_map(
            inner, mesh=mesh, in_specs=(spec, mesh_lib.batch_pspec()),
            out_specs=learner_lib.StepOutput(
                state=spec, td_errors=P("data"),
                metrics={k: P() for k in learner_lib.metric_keys(cfg)},
            ),
        )
    else:
        step = learner_lib.make_learner_step(cfg, 1.5, action_offset=0.25)
    shardings = (learner._state_sharding, learner._chunk_sharding)

    def plain(s, packed):
        def body(c, b):
            out = step(c, b)
            return out.state, (out.td_errors, out.metrics)

        s, (tds, ms) = jax.lax.scan(
            body, s, unpack_batch(packed, OBS, ACT), unroll=learner.unroll
        )
        return s, tds, learner_lib.chunk_metrics(ms)

    if not learner.guard_enabled:
        return jax.jit(plain, in_shardings=shardings)
    gstep = guardrails.make_guarded_step(
        step, zmax=cfg.guardrail_zmax, warmup=cfg.guardrail_warmup_steps,
        inject=learner._numeric_inject,
    )

    def guarded(s, packed, g):
        pre_bad, _, _ = guardrails.batch_row_health(packed, None)

        def body(c, x):
            ns, ng, td, ms = gstep(*c, *x)
            return (ns, ng), (td, ms)

        (s, g), (tds, ms) = jax.lax.scan(
            body, (s, g), (unpack_batch(packed, OBS, ACT), pre_bad),
            unroll=learner.unroll,
        )
        return s, tds, learner_lib.chunk_metrics(ms), g

    return jax.jit(guarded, in_shardings=shardings + (None,))


def _run_both(chunked, in_steps, rep, per=False, beta=0.4):
    """One chunk of `chunked` on `rep`, and the same K updates through
    `in_steps`: ((state, TD errors, metrics[, guard]) of each, the rows)."""
    rows = _chunk_rows(chunked, rep, per, beta)
    guard = (jax.device_get(chunked._guard),) if chunked.guard_enabled else ()
    want = in_steps(jax.device_get(chunked.state), rows, *guard)
    out = (
        chunked.run_sample_chunk_per(rep, beta) if per
        else chunked.run_sample_chunk(rep)
    )
    got = (out.state, out.td_errors, out.metrics)
    if chunked.guard_enabled:
        got += (chunked._guard,)
    return jax.device_get(got), jax.device_get(want), rows


def _assert_same(got, want, exact):
    def check(x, y):
        if exact:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        else:  # tests/test_fused_chunk.py's TD3 tolerance for the two legs
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=2e-4, atol=1e-5
            )

    jax.tree.map(check, got, want)


def _by_hand(algo, seed, step, device=None):
    """One step's noise from jax.random alone."""
    const = 0x5AC0 if algo == "sac" else 0x7D3AF
    key = jax.random.fold_in(jax.random.PRNGKey(seed ^ const), step)
    if device is not None:
        key = jax.random.fold_in(key, device)
    if algo == "sac":
        k_next, k_cur = jax.random.split(key)
        return (
            jax.random.normal(k_next, (B, ACT)),
            jax.random.normal(k_cur, (B, ACT)),
        )
    return jnp.clip(0.2 * jax.random.normal(key, (B, ACT)), -0.5, 0.5)


_by_hand_jit = jax.jit(_by_hand, static_argnums=(0, 1, 3))


@pytest.mark.parametrize("device", [None, 1])
@pytest.mark.parametrize("algo", list(ALGOS))
def test_chunk_noise_is_the_step_stream_row_by_row(algo, device):
    cfg = _cfg(algo)
    assert learner_lib.draws_noise(cfg) and cfg.target_noise_clip == 0.5
    step0 = 37
    got = jax.jit(
        lambda s: learner_lib.chunk_noise(
            cfg, learner_lib.noise_base_key(cfg), s, K, B, ACT, device
        )
    )(jnp.int32(step0))
    for k in range(K):
        want = _by_hand_jit(algo, cfg.seed, step0 + k, device)
        _assert_same(jax.tree.map(lambda x: x[k], got), want, exact=True)
    # What a single step draws for itself is the same function of the step.
    base = learner_lib.noise_base_key(cfg)
    one = jax.jit(
        lambda: learner_lib.step_noise(cfg, base, step0 + 3, B, ACT, device)
    )()
    _assert_same(one, jax.tree.map(lambda x: x[3], got), exact=True)


@pytest.mark.parametrize("algo", list(QUIET))
def test_quiet_algorithms_draw_nothing(algo):
    cfg = _cfg(algo)
    assert not learner_lib.draws_noise(cfg)
    base = learner_lib.noise_base_key(cfg)
    assert base is None
    assert learner_lib.chunk_noise(cfg, base, 0, K, B, ACT) is None
    assert learner_lib.step_noise(cfg, base, 0, B, ACT) is None


@pytest.mark.parametrize(
    "variant", [v for v in VARIANTS if v != "per_guarded"]
)
@pytest.mark.parametrize("algo", list(ALGOS))
def test_chunk_with_predrawn_noise_equals_k_single_steps(algo, variant):
    devices, _, per, _ = VARIANTS[variant]
    beta = 0.4
    chunked, ref = _learner(algo, variant), _learner(algo, variant, chunk=1)
    in_steps = _scan_that_draws_in_its_steps(chunked)
    rep = _replay(chunked, per)
    for _ in range(2):  # the second chunk starts at step K, not 0
        got, want, rows = _run_both(chunked, in_steps, rep, per, beta)
        _assert_same(got, want, exact=not (algo == "sac" and devices == 2))
        _assert_same(got[:3], _k_single_steps(ref, rows), exact=False)
    assert int(chunked.state.step) == 2 * K
    if chunked.guard_enabled:
        assert chunked.poll_health()["skipped"] == 0


@pytest.mark.parametrize("algo", list(ALGOS))
def test_guarded_chunk_that_drops_a_step_stays_aligned(algo):
    """Guarded step 3 sees a NaN batch and is dropped; the step counter
    still advances, so steps 4 .. K-1 and the whole next chunk draw the
    noise their step numbers name."""
    chunked = _learner(algo, "guarded", faults="numeric:grad:nan@3")
    in_steps = _scan_that_draws_in_its_steps(chunked)
    rep = _replay(chunked, per=False)
    for chunk in range(2):
        got, want, _ = _run_both(chunked, in_steps, rep)
        _assert_same(got, want, exact=True)
        assert int(got[0].step) == (chunk + 1) * K
        # Update 3 of the first chunk is the one thrown away, and no other.
        dropped = np.flatnonzero(np.all(got[1] == 0.0, axis=1))
        assert list(dropped) == ([2] if chunk == 0 else [])
    health = chunked.poll_health()
    assert health["skipped"] == 1 and health["nonfinite"] == 1


# --- structure: what the traced programs hold, and where ---


def _primitives(jaxpr, in_scan, found):
    """[(primitive, inside a scan body?)] and the scans' xs counts."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        found["prims"].append((name, in_scan))
        if name == "scan":
            p = eqn.params
            xs = len(eqn.invars) - p["num_consts"] - p["num_carry"]
            if xs:  # the PER draw's searchsorted is a scan over nothing
                found["scan_xs"].append(xs)
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else (val,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, in_scan or name == "scan", found)


def _chunk_program(learner, variant):
    """(the jitted chunk program, its arguments) as run_sample_chunk[_per]
    calls it."""
    _, _, per, guarded = VARIANTS[variant]
    rep = _replay(learner, per)
    guard = (learner._guard,) if guarded else ()
    if per:
        args = (
            learner.state, learner._key, *rep.per_state(),
            np.float32(0.4), np.float32(rep.alpha), np.float32(rep.eps),
        )
        return learner._per_sample_chunk_step, args + guard
    return (
        learner._sample_chunk_step,
        (learner.state, learner._key, *rep.device_state()) + guard,
    )


def _traced(learner, variant):
    found = {"prims": [], "scan_xs": []}
    fn, args = _chunk_program(learner, variant)
    _primitives(jax.make_jaxpr(fn)(*args).jaxpr, False, found)
    return found


@pytest.mark.parametrize("variant", list(VARIANTS))
@pytest.mark.parametrize("algo", list(ALGOS))
def test_no_rng_primitive_inside_the_scan_body(algo, variant):
    found = _traced(_learner(algo, variant), variant)
    rng = [(p, inside) for p, inside in found["prims"] if p in RNG_PRIMITIVES]
    assert [p for p, inside in rng if inside] == []
    # The launch's draw is in the same program, before the scan: the noise's
    # normals besides the index draw's one random_bits.
    assert sum(p == "random_bits" for p, _ in rng) >= 2
    assert any(p == "random_fold_in" for p, _ in rng)
    # One scan, over the six fields of the batches, the guard's row screen
    # where there is one, and the noise (SAC: two arrays; TD3: one).
    _, _, _, guarded = VARIANTS[variant]
    assert found["scan_xs"] == [6 + guarded + (2 if algo == "sac" else 1)]


@pytest.mark.parametrize("variant", ["uniform", "per", "guarded", "explicit"])
@pytest.mark.parametrize("algo", list(QUIET))
def test_quiet_scan_chunks_scan_over_the_batches_alone(algo, variant):
    found = _traced(_learner(algo, variant), variant)
    _, _, _, guarded = VARIANTS[variant]
    assert found["scan_xs"] == [6 + guarded]
    assert not any(p == "random_fold_in" for p, _ in found["prims"])


def test_hostfed_chunk_draws_before_its_scan_too():
    """`_chunk_step`, the chunk fed from the host, goes through the same
    scan_steps."""
    learner = _learner("sac", "uniform")
    packed = jnp.zeros((K, B, 2 * OBS + ACT + 3), jnp.float32)
    found = {"prims": [], "scan_xs": []}
    _primitives(
        jax.make_jaxpr(learner._chunk_step)(learner.state, packed).jaxpr,
        False, found,
    )
    rng = [(p, inside) for p, inside in found["prims"] if p in RNG_PRIMITIVES]
    assert rng and not any(inside for _, inside in rng)
    assert found["scan_xs"] == [8]


# --- the base key is an argument: one text for every seed, the same bits ---

HOSTFED = dict(hostfed=True)
KERNEL = dict(fused_chunk="on")  # the megakernel, in interpret mode here
# case -> (algo, variant, what else the learner is built with)
SEEDLESS = {
    "sac-uniform": ("sac", "uniform", {}),
    "sac-explicit": ("sac", "explicit", {}),
    "sac-auto_mesh2": ("sac", "auto_mesh2", {}),
    "redq-uniform": ("redq", "uniform", {}),
    "redq-explicit": ("redq", "explicit", {}),
    "crossq-uniform": ("crossq", "uniform", {}),
    "crossq-explicit": ("crossq", "explicit", {}),
    "sac-guarded": ("sac", "guarded", {}),
    "sac-per": ("sac", "per", {}),
    "td3-per_guarded": ("td3", "per_guarded", {}),
    "td3-uniform": ("td3", "uniform", {}),
    "td3-kernel": ("td3", "uniform", KERNEL),
    "td3-kernel-per": ("td3", "per", KERNEL),
    "sac-kernel": ("sac", "uniform", KERNEL),
    "td3-fused_mesh": ("td3", "auto_mesh2", KERNEL),
    "sac-hostfed": ("sac", "uniform", HOSTFED),
    "td3-hostfed-guarded": ("td3", "guarded", HOSTFED),
}
CONTROLS = {
    "ddpg-uniform": ("ddpg", "uniform", {}),
    "ddpg-kernel": ("ddpg", "uniform", KERNEL),
    "ddpg-hostfed": ("ddpg", "uniform", HOSTFED),
    "d4pg-uniform": ("d4pg", "uniform", {}),
    "d4pg-kernel": ("d4pg", "uniform", KERNEL),
    "d4pg-explicit": ("d4pg", "explicit", {}),
}


def _lowered(case, seed):
    """(the text the case's chunk program lowers to at `seed`, the leaves of
    the arguments a launch hands it, the learner)."""
    algo, variant, extra = {**SEEDLESS, **CONTROLS}[case]
    extra = dict(extra)
    hostfed = extra.pop("hostfed", False)
    learner = _learner(algo, variant, seed=seed, **extra)
    assert learner.fused_chunk_active == (extra.get("fused_chunk") == "on")
    if hostfed:
        packed = jnp.zeros(
            (K, learner.global_batch, 2 * OBS + ACT + 3), jnp.float32
        )
        guard = (learner._guard,) if learner.guard_enabled else ()
        fn, args = learner._chunk_step, (learner.state, packed) + guard
    else:
        fn, args = _chunk_program(learner, variant)
    return fn.lower(*args).as_text(), len(jax.tree.leaves(args)), learner


def _main_parameters(text):
    """The types of the lowered module's public parameters."""
    from distributed_ddpg_tpu.analysis.programs import _main_signature

    return re.findall(r"%arg\d+: tensor<([^>]*)>", _main_signature(text)[0])


@pytest.mark.parametrize("case", list(SEEDLESS))
def test_a_chunk_program_that_draws_lowers_to_one_text_at_every_seed(case):
    """Fails on a tree whose chunk programs hold PRNGKey(seed ^ const) as a
    constant: the persistent compile cache keys on this text."""
    one, leaves, learner = _lowered(case, seed=1)
    two, _, other = _lowered(case, seed=2)
    assert one == two
    # The base keys differ, and reach the program as its last parameter.
    assert not np.array_equal(learner._noise_key, other._noise_key)
    np.testing.assert_array_equal(
        learner._noise_key, learner_lib.noise_base_key(learner.config)
    )
    params = _main_parameters(one)
    assert len(params) == leaves + 1 and params[-1] == "2xui32"
    assert params.count("2xui32") == 1 + ("hostfed" not in case)


@pytest.mark.parametrize("case", list(CONTROLS))
def test_a_chunk_program_that_draws_nothing_has_no_key_parameter(case):
    """DDPG and D4PG keep the signature they had: None is an empty pytree,
    so the text is the one the call gives with no key argument at all."""
    one, leaves, learner = _lowered(case, seed=1)
    two, _, _ = _lowered(case, seed=2)
    assert learner._noise_key is None and one == two
    params = _main_parameters(one)
    # No key but the sampling key (the chunk fed from the host has none).
    assert len(params) == leaves
    assert params.count("2xui32") == ("hostfed" not in case)


STREAMS = {**ALGOS, "redq": FAMILIES["redq"]}


@pytest.mark.parametrize("device", [None, 1])
@pytest.mark.parametrize("seed", [7, 2_000_000_011])
@pytest.mark.parametrize("algo", list(STREAMS))
def test_the_stream_of_a_traced_base_key_is_the_stream_of_the_seed(
    algo, seed, device
):
    """chunk_noise with the base handed in under jit, as the chunk programs
    hand it in, against the step's own draw from the constant key, row by
    row, to the bit: the stream did not move when the key became an
    argument."""
    cfg = _cfg(algo, seed=seed)
    base = learner_lib.noise_base_key(cfg)
    step0 = 37
    got = jax.jit(
        lambda key, s: learner_lib.chunk_noise(cfg, key, s, K, B, ACT, device)
    )(base, jnp.int32(step0))
    assert len(jax.tree.leaves(got)) == (1 if algo == "td3" else 2 + (algo == "redq"))
    for k in range(K):
        want = jax.jit(
            lambda: learner_lib.step_noise(cfg, base, step0 + k, B, ACT, device)
        )()
        _assert_same(jax.tree.map(lambda x: x[k], got), want, exact=True)
        if algo != "redq":
            _assert_same(
                want, _by_hand_jit(algo, seed, step0 + k, device), exact=True
            )
