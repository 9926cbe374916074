"""D4PG against its plain reference (benchmarks/reference/d4pg.py), at a
small size on the CPU: the scan step and the interpreted megakernel follow
the reference's updates on seeded weights; the program's projection agrees
with the reference's dense form; five-step rows carry the hand sums.

The reference is loaded from its one file under benchmarks/, by path, so
there is no second copy to drift.
"""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ddpg_tpu.config import DDPGConfig

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")

ENV = {"obs_dim": 17, "act_dim": 6, "action_scale": 1.0, "action_offset": 0.0}
HP = {
    "hidden": [32, 32], "gamma": 0.99, "tau": 0.001, "actor_lr": 1e-4, "critic_lr": 1e-4,
    "batch_size": 16, "num_atoms": 51, "v_min": -150.0, "v_max": 150.0,
}
UPDATES, SEED = 8, 11


@pytest.fixture(scope="module")
def d4pg():
    sys.path.insert(0, BENCH)
    try:
        return importlib.import_module("reference.d4pg")
    finally:
        sys.path.remove(BENCH)


def config(**kw):
    return DDPGConfig(
        distributional=True, num_atoms=HP["num_atoms"], v_min=HP["v_min"], v_max=HP["v_max"], n_step=5,
        actor_hidden=tuple(HP["hidden"]), critic_hidden=tuple(HP["hidden"]), batch_size=HP["batch_size"],
        actor_lr=HP["actor_lr"], critic_lr=HP["critic_lr"], tau=HP["tau"], seed=SEED, **kw,
    )


def rows(seed, n, ret_scale=4.0):
    """Packed rows [obs | action | R | d | next_obs | w] with five-step
    returns of HalfCheetah's size, a few terminal rows, weights 1."""
    o, a = ENV["obs_dim"], ENV["act_dim"]
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    obs = jax.random.normal(k[0], (n, o))
    ret = ret_scale * jax.random.normal(k[2], (n, 1))
    disc = 0.99**5 * (jax.random.uniform(k[3], (n, 1)) > 0.05)
    return jnp.concatenate(
        [obs, jax.random.uniform(k[1], (n, a), minval=-1.0, maxval=1.0), ret, disc,
         obs + 0.1 * jax.random.normal(k[4], (n, o)), jnp.ones((n, 1))], axis=1,
    ).astype(jnp.float32)


def program_chunk(leg, batches):
    """(state before, state after, td [K, B], chunk-mean metrics, per-update
    metrics or None where the leg reports only means) from the program's own
    update on `batches` [K, B, width]."""
    from distributed_ddpg_tpu.learner import init_train_state, make_learner_step, metric_keys
    from distributed_ddpg_tpu.ops import fused_chunk
    from distributed_ddpg_tpu.types import unpack_batch

    cfg = config(fused_chunk="on" if leg == "kernel" else "off")
    s0 = init_train_state(cfg, ENV["obs_dim"], ENV["act_dim"], SEED)
    if leg == "kernel":
        run = fused_chunk.make_fused_chunk_fn(
            cfg, ENV["obs_dim"], ENV["act_dim"], ENV["action_scale"], ENV["action_offset"],
            chunk_size=UPDATES, interpret=True,
        )
        s1, td, metrics = jax.jit(run)(s0, batches)
        per_update = None
    else:
        step = make_learner_step(cfg, ENV["action_scale"], action_offset=ENV["action_offset"])

        def body(s, packed):
            out = step(s, unpack_batch(packed, ENV["obs_dim"], ENV["act_dim"]))
            return out.state, (out.td_errors, out.metrics)

        from distributed_ddpg_tpu.learner import chunk_metrics

        s1, (td, per_update) = jax.jit(lambda s, b: jax.lax.scan(body, s, b))(s0, batches)
        metrics = chunk_metrics(per_update)
    assert set(metrics) == set(metric_keys(cfg))
    return s0, s1, td, metrics, per_update


def view(state):
    return {"actor": state.actor_params, "critic": state.critic_params,
            "target_actor": state.target_actor_params, "target_critic": state.target_critic_params}


@pytest.mark.parametrize("leg", ["scan", "kernel"])
def test_program_follows_the_reference_over_eight_updates(d4pg, leg):
    batches = rows(3, UPDATES * HP["batch_size"]).reshape(UPDATES, HP["batch_size"], -1)
    s0, s1, td, metrics, per_update = program_chunk(leg, batches)
    ref0 = d4pg.init(SEED, ENV, HP)
    ref1, ref = jax.jit(lambda s, b: jax.lax.scan(d4pg.make_step(SEED, ENV, HP), s, b))(ref0, batches)

    # Both sides are float32 on the CPU, so what is left is the order of
    # rounding: the reference multiplies at Precision.HIGHEST and sums the
    # projection over a [B, A, A] array, the program uses XLA:CPU's default
    # dot and a floor/ceil scatter (scan) or a loop over atoms (kernel).
    for k in view(s0):  # the seeded weights: the same keys, the same draws
        for a, b in zip(jax.tree.leaves(view(s0)[k]), jax.tree.leaves(ref0[k])):
            np.testing.assert_array_equal(a, b)
    # update 0's td, sample by sample: the forward pass of critic, target and
    # policy, the projection's expectation and the softmax, on returns of
    # size 10 over a support 300 wide: 1e-4 absolute is 30 float32 epsilons
    # of the support's width.
    np.testing.assert_allclose(td[0], ref["td"][0], atol=1e-4, rtol=0)
    # every update's td: eight Adam steps of 1e-4 carry the rounding on
    np.testing.assert_allclose(td, ref["td"], atol=3e-4, rtol=0)
    # the losses: the cross-entropy near log(51) = 3.93 to 1e-5 relative (a
    # projection that lost or misplaced mass would move it in the second
    # digit), the actor's loss (a mean expectation near 0, |z| up to 150) to
    # 1e-4 absolute; update by update where the leg reports them so (the
    # kernel accumulates the chunk's mean inside the launch)
    assert float(metrics["critic_loss"]) == pytest.approx(float(jnp.mean(ref["critic_loss"])), rel=1e-5)
    assert float(metrics["actor_loss"]) == pytest.approx(float(jnp.mean(ref["actor_loss"])), abs=1e-4)
    if per_update is not None:
        np.testing.assert_allclose(per_update["critic_loss"], ref["critic_loss"], rtol=1e-5)
        np.testing.assert_allclose(per_update["actor_loss"], ref["actor_loss"], atol=1e-4, rtol=0)
    # every net's change over the chunk, leaf by leaf, to 1% of the leaf's
    # own change or of the net's median leaf's: Adam's first steps divide a
    # gradient by its own size, which turns a rounding in a near-zero
    # gradient into a visible share of one step
    for k in view(s1):
        d_ref = [np.asarray(b1 - b0) for b1, b0 in zip(jax.tree.leaves(ref1[k]), jax.tree.leaves(ref0[k]))]
        d_prog = [np.asarray(a1 - a0) for a1, a0 in zip(jax.tree.leaves(view(s1)[k]), jax.tree.leaves(view(s0)[k]))]
        floor = np.median([np.linalg.norm(d) for d in d_ref])
        for dr, dp in zip(d_ref, d_prog):
            assert np.linalg.norm(dp - dr) <= 0.01 * max(np.linalg.norm(dr), floor), k
    # the edge mass is the projection's own: returns of size 10 on +-150
    # put almost nothing on the end atoms, and both legs say so
    assert 0.0 <= float(metrics["c51_edge_mass"]) < 0.05


@pytest.mark.parametrize("leg", ["scan", "kernel"])
def test_edge_mass_is_the_last_updates_and_the_references(d4pg, leg):
    """`c51_edge_mass` on rows whose returns reach past the support (sigma
    120 on +-150, so about a fifth of the target's mass lies on the end
    atoms): a chunk reports its LAST update's batch mean (the kernel computes
    it on its last grid step only, `learner.chunk_metrics` takes the scan's
    last), and that is the reference's dense `project()` on the same rows
    under the same eight updates. Float32 on both sides: a sum of 16 rows'
    two end atoms, 1e-5 absolute."""
    batches = rows(4, UPDATES * HP["batch_size"], ret_scale=120.0).reshape(UPDATES, HP["batch_size"], -1)
    _, _, _, metrics, per_update = program_chunk(leg, batches)
    _, ref = jax.jit(lambda s, b: jax.lax.scan(d4pg.make_step(SEED, ENV, HP), s, b))(d4pg.init(SEED, ENV, HP), batches)
    assert 0.1 < float(ref["edge_mass"][-1]) < 0.5
    assert abs(float(ref["edge_mass"][-1]) - float(ref["edge_mass"][0])) > 1e-3  # the updates differ: "last" is a claim
    assert float(metrics["c51_edge_mass"]) == pytest.approx(float(ref["edge_mass"][-1]), abs=1e-5)
    if per_update is not None:
        np.testing.assert_allclose(per_update["c51_edge_mass"], ref["edge_mass"], atol=1e-5, rtol=0)


def dense(d4pg, probs, ret, disc):
    return d4pg.project(HP, jnp.asarray(probs), jnp.asarray(ret, jnp.float32), jnp.asarray(disc, jnp.float32))


def test_projection_against_the_dense_form(d4pg):
    from distributed_ddpg_tpu.ops import losses

    z = losses.categorical_support(HP["v_min"], HP["v_max"], HP["num_atoms"])
    dz = 300.0 / 50
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(5), (64, 51)), axis=-1)
    ret = 40.0 * jax.random.normal(jax.random.PRNGKey(6), (64,))
    disc = 0.99**5 * (jax.random.uniform(jax.random.PRNGKey(7), (64,)) > 0.2)
    ours, theirs = losses.categorical_projection(z, probs, ret, disc), dense(d4pg, probs, ret, disc)
    np.testing.assert_allclose(ours, theirs, atol=2e-6, rtol=0)
    np.testing.assert_allclose(jnp.sum(theirs, axis=-1), 1.0, atol=1e-5)  # mass sums to 1
    # R = 0, d = 1 moves nothing
    np.testing.assert_allclose(dense(d4pg, probs, jnp.zeros(64), jnp.ones(64)), probs, atol=1e-6)
    # d = 0 puts all mass on the two atoms round R (here R = 7: atoms 26 and 27, 5/6 and 1/6)
    m = dense(d4pg, probs[:1], [7.0], [0.0])[0]
    lo = int((7.0 - HP["v_min"]) // dz)
    assert lo == 26 and m[lo] == pytest.approx(1 - 1 / 6, abs=1e-5) and m[lo + 1] == pytest.approx(1 / 6, abs=1e-5)
    assert float(jnp.sum(m)) == pytest.approx(1.0, abs=1e-5)
    np.testing.assert_allclose(losses.categorical_projection(z, probs[:1], jnp.array([7.0]), jnp.array([0.0]))[0], m, atol=2e-6)
    # R beyond v_max lands on the last atom
    top = dense(d4pg, probs[:1], [1e4], [0.99])[0]
    assert top[-1] == pytest.approx(1.0, abs=1e-6) and float(jnp.sum(top[:-1])) == pytest.approx(0.0, abs=1e-6)


# --- the megakernel's projection (ops/fused_chunk.kernel_projection: atoms on
# sublanes, batch on lanes, one transpose in and one out), run as a Pallas
# kernel in interpret mode, against the scan leg's floor/ceil form
# (ops/losses.py) and the reference's dense one. Cases counted singly. ---

KINDS = ("returns", "on_an_atom", "below_v_min", "above_v_max", "terminal")


def projection_rows(kind, batch, z):
    """(ret, disc) [B]: what a ring of 5-step rows holds, and its corners."""
    atoms = z.shape[0]
    k = jax.random.split(jax.random.PRNGKey(batch + atoms), 3)
    ret = 40.0 * jax.random.normal(k[0], (batch,))
    disc = jnp.full((batch,), 0.99**5)
    if kind == "on_an_atom":  # d = 0 and R = z_k: weight 1 on atom k, 0 elsewhere
        ret = z[jax.random.randint(k[1], (batch,), 0, atoms)]
        disc = jnp.zeros((batch,))
    elif kind == "below_v_min":  # every atom clips to v_min: what c51_edge_mass reads
        ret = jnp.full((batch,), -1e4)
    elif kind == "above_v_max":
        ret = jnp.full((batch,), 1e4)
    elif kind == "terminal":  # d = 0 rows among the others: all mass round R
        disc = disc * (jax.random.uniform(k[2], (batch,)) > 0.5)
    return ret.astype(jnp.float32), disc.astype(jnp.float32)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("atoms", [51, 21])
@pytest.mark.parametrize("batch", [64, 100, 256])
def test_kernel_projection_against_both_dense_forms(d4pg, batch, atoms, kind):
    from jax.experimental import pallas as pl

    from distributed_ddpg_tpu.ops import fused_chunk, losses

    hp = {**HP, "num_atoms": atoms}
    v_min, v_max = HP["v_min"], HP["v_max"]
    dz = (v_max - v_min) / (atoms - 1)
    z = losses.categorical_support(v_min, v_max, atoms)
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(atoms), (batch, atoms)), axis=-1)
    ret, disc = projection_rows(kind, batch, z)

    def body(p_ref, rd_ref, zc_ref, out_ref):
        rd = rd_ref[...]
        out_ref[...] = fused_chunk.kernel_projection(p_ref[...], rd[0:1, :], rd[1:2, :], zc_ref[...], v_min, v_max)

    ours = pl.pallas_call(body, out_shape=jax.ShapeDtypeStruct((batch, atoms), jnp.float32), interpret=True)(
        probs, jnp.stack([ret, disc]), z.reshape(-1, 1)
    )
    # float32 on both sides; a sum of `atoms` terms in the atoms' order against
    # XLA:CPU's own order of the dense sums
    np.testing.assert_allclose(ours, losses.categorical_projection(z, probs, ret, disc), atol=5e-6, rtol=0)
    np.testing.assert_allclose(ours, d4pg.project(hp, probs, ret, disc), atol=5e-6, rtol=0)
    np.testing.assert_allclose(jnp.sum(ours, axis=-1), 1.0, atol=1e-5)
    total = np.asarray(jnp.sum(probs, axis=-1))
    if kind == "on_an_atom":
        # one atom holds the row's mass; its neighbours a millionth at most,
        # where linspace's atoms lie an ulp off a multiple of dz
        at = np.asarray(jnp.round((ret - v_min) / dz).astype(jnp.int32))
        np.testing.assert_allclose(np.asarray(ours)[np.arange(batch), at], total, atol=5e-6, rtol=0)
        assert (np.count_nonzero(np.asarray(ours) > 5e-6, axis=-1) == 1).all()
    elif kind in ("below_v_min", "above_v_max"):
        end = 0 if kind == "below_v_min" else atoms - 1
        np.testing.assert_allclose(np.asarray(ours)[:, end], total, atol=5e-6, rtol=0)
        assert np.count_nonzero(np.asarray(ours)) == batch
    elif kind == "terminal":  # a d = 0 row has at most two atoms, neighbours
        rows_ = np.asarray(ours)[np.asarray(disc) == 0]
        assert len(rows_) and (np.count_nonzero(rows_, axis=-1) <= 2).all()


def test_five_step_rows_carry_the_hand_sums():
    """One seeded episode of 9 steps that terminates, then one of 7 that is
    truncated, through the actor's accumulator and its truncation flush."""
    from distributed_ddpg_tpu.actors.worker import _flush_truncated
    from distributed_ddpg_tpu.replay.nstep import NStepAccumulator

    n, g = 5, 0.99
    rng = np.random.default_rng(SEED)
    acc = NStepAccumulator(n, g)

    def episode(length, terminated):
        obs = rng.normal(size=(length + 1, 3)).astype(np.float32)
        rew = rng.normal(size=length)
        out = []
        for t in range(length):
            done = terminated and t == length - 1
            out += list(acc.push(obs[t][None], np.zeros((1, 1), np.float32), [rew[t]], [done], obs[t + 1][None]))
        if not terminated:
            out += _flush_truncated(acc, obs[length])
        acc.reset()
        return obs, rew, out

    for length, terminated in ((9, True), (7, False)):
        obs, rew, out = episode(length, terminated)
        assert len(out) == length  # one row per step: nothing stranded, nothing twice
        by_start = {}
        for o, a, r, d, nobs in out:
            t = int(np.argmax((obs[:-1] == o).all(axis=1)))
            by_start[t] = (r, d, nobs)
        assert sorted(by_start) == list(range(length))
        for t, (r, d, nobs) in by_start.items():
            steps = min(n, length - t)
            assert r == pytest.approx(sum(g**k * rew[t + k] for k in range(steps)), rel=1e-6)
            ends_here = t + steps == length
            assert d == pytest.approx(0.0 if (terminated and ends_here) else g**steps, rel=1e-6)
            np.testing.assert_array_equal(nobs, obs[t + steps])
        # rows emitted, and how many carry fewer than n steps (the episode's last n - 1)
        assert acc.rows == length and acc.short_rows == n - 1
        acc.rows = acc.short_rows = 0


@pytest.mark.parametrize("dtype", ["bfloat16", "float8_e5m2"])
def test_products_round_as_the_shared_reference_does(d4pg, dtype):
    """`d4pg.products` rounds with `lax.reduce_precision` where
    `common.products` casts to the dtype and back (the float8 arrays turn
    the whole update into NaN under the TPU's compiler at 400-300): the same
    product and the same two cotangents, bit for bit, on operands the dtype
    holds as normal numbers; what float8_e5m2 holds as a subnormal goes to
    zero."""
    k = jax.random.split(jax.random.PRNGKey(2), 5)

    def normal_range(key, shape):  # magnitudes in [0.01, 4): normal numbers in both dtypes
        ka, kb = jax.random.split(key)
        return jax.random.uniform(ka, shape, minval=0.01, maxval=4.0) * jnp.sign(jax.random.normal(kb, shape))

    x, w, g = normal_range(k[0], (16, 40)), normal_range(k[1], (40, 24)), normal_range(k[2], (16, 24))
    ours, theirs = (jax.vjp(f(dtype), x, w) for f in (d4pg.products, d4pg.c.products))
    np.testing.assert_array_equal(ours[0], theirs[0])
    for a, b in zip(ours[1](g), theirs[1](g)):
        np.testing.assert_array_equal(a, b)
    assert float(jnp.max(jnp.abs(ours[0] - jnp.dot(x, w, precision="highest")))) > 0  # it does round
    if dtype == "float8_e5m2":
        tiny = jnp.full((1, 1), 3e-5)  # under float8_e5m2's smallest normal, 6.1e-5
        assert float(d4pg.products(dtype)(tiny, jnp.ones((1, 1)))[0, 0]) == 0.0
        assert float(d4pg.c.products(dtype)(tiny, jnp.ones((1, 1)))[0, 0]) > 0.0
