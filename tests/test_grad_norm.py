"""The gradient norms of the learner step (learner.optree_norm), since PR 46
with ONE scalar a norm leaving the vector unit: each leaf's squares summed
down to its last axis, the rows added across the leaves, one sum rooted. On
the chip a scalar that a fusion hands the scalar core costs the scan's loop
0.35-0.7 us, and a sum a leaf was twelve of them a SAC update (PERF.md §6).

- the number is the norm: against float64 numpy on every family's own
  gradient shapes (stacked ensembles, one-wide heads, batch-norm and
  residual trees, a scalar), and against the sum a leaf it replaces, to
  float32 rounding;
- the structure: one reduction to a `[]` shape in its jaxpr, whatever the
  tree;
- a chunk of K = 8 through scan_chunk ends in the TrainState, the TD errors
  and the six metrics of K dispatches of the single-step program, at the
  tolerance tests/test_learner_noise.py holds the two to (XLA:CPU contracts
  a scan's body differently from a program of one step), and the counts
  advance by K, for the six families whose scan step a cell or a leg runs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ddpg_tpu import learner as learner_lib
from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.parallel.learner import scan_chunk
from distributed_ddpg_tpu.types import unpack_batch

OBS, ACT, B, K = 5, 3, 16, 8  # K past the scan's unroll of 4: the loop stays a loop
SCALE, OFFSET = 1.5, 0.25
PLAIN = dict(actor_hidden=(16, 16), critic_hidden=(16, 16))
FAMILIES = {
    "sac": dict(sac=True, **PLAIN),
    "redq": dict(sac=True, critic_ensemble=5, target_subset=2, policy_delay=3, **PLAIN),
    "crossq": dict(sac=True, crossq=True, policy_delay=3, adam_b1=0.5, action_insert_layer=0, **PLAIN),
    "simba": dict(
        sac=True, simba=True, actor_hidden=(16,), critic_hidden=(32, 32), weight_decay=1e-2,
        sac_alpha=0.01, target_entropy_scale=0.5, action_insert_layer=0,
    ),
    "td3": dict(twin_critic=True, target_noise=0.2, policy_delay=2, **PLAIN),
    "ddpg": dict(**PLAIN),
}
DELAYED = {"redq": 3, "crossq": 3, "td3": 2}


def _cfg(family):
    return DDPGConfig(batch_size=B, seed=7, fused_chunk="off", **FAMILIES[family])


def _sum_a_leaf(tree):
    """The norm as it was: a sum a leaf, added up as scalars."""
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def _like(tree, seed):
    """Seeded normal values in the shapes of `tree`'s leaves, a few of them large."""
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda x: jnp.asarray(rng.standard_normal(x.shape) * rng.choice([1e-3, 1.0, 30.0]), jnp.float32), tree
    )


@pytest.mark.parametrize("net", ["actor", "critic"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_norm_is_the_norm_on_every_familys_gradient_shapes(family, net):
    state = learner_lib.init_train_state(_cfg(family), OBS, ACT, 0)
    grads = _like(getattr(state, f"{net}_params"), seed=len(family))
    want = np.sqrt(sum(np.sum(np.square(np.asarray(x, np.float64))) for x in jax.tree.leaves(grads)))
    got = jax.jit(learner_lib.optree_norm)(grads)
    assert got.shape == () and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(got, jax.jit(_sum_a_leaf)(grads), rtol=2e-6)


def test_the_norm_of_odd_trees():
    # a scalar leaf, a one-wide leaf, a leaf wider than the rest, zeros
    tree = {"a": jnp.float32(3.0), "b": jnp.full((4, 1), 2.0), "c": jnp.ones((2, 3, 7)), "d": jnp.zeros((5,))}
    np.testing.assert_allclose(learner_lib.optree_norm(tree), np.sqrt(9.0 + 16.0 + 42.0), rtol=1e-6)
    assert float(learner_lib.optree_norm({"w": jnp.zeros((3, 4))})) == 0.0
    assert float(learner_lib.optree_norm(jnp.float32(-2.5))) == 2.5


def _scalar_reductions(jaxpr):
    return [
        eqn for eqn in jaxpr.eqns
        if eqn.primitive.name.startswith("reduce_") and eqn.outvars[0].aval.shape == ()
    ]


@pytest.mark.parametrize("family", ["sac", "redq", "simba"])
def test_one_scalar_leaves_the_vector_unit_a_norm(family):
    state = learner_lib.init_train_state(_cfg(family), OBS, ACT, 0)
    for tree in (state.actor_params, state.critic_params):
        assert len(jax.tree.leaves(tree)) >= 6
        assert len(_scalar_reductions(jax.make_jaxpr(learner_lib.optree_norm)(tree).jaxpr)) == 1
        # a sum a leaf had one a leaf
        assert len(_scalar_reductions(jax.make_jaxpr(_sum_a_leaf)(tree).jaxpr)) == len(jax.tree.leaves(tree))


def _rows(chunks):
    rng = np.random.default_rng(3)
    rows = rng.standard_normal((chunks, K, B, 2 * OBS + ACT + 3)).astype(np.float32)
    rows[..., OBS + ACT + 1] = 0.99  # discount
    rows[..., -1] = 1.0              # weight
    return rows


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_chunk_through_scan_chunk_ends_where_k_single_steps_end(family):
    cfg = _cfg(family)
    keys = learner_lib.metric_keys(cfg)
    assert keys[:6] == learner_lib.METRIC_KEYS
    step = learner_lib.make_learner_step(cfg, SCALE, action_offset=OFFSET)

    def run(s, packed):
        noise = learner_lib.chunk_noise(cfg, learner_lib.noise_base_key(cfg), s.step, K, B, ACT)
        return scan_chunk(step, s, unpack_batch(packed, OBS, ACT), noise, unroll=4)

    chunk, single = jax.jit(run), jax.jit(step)
    s_c = s_1 = learner_lib.init_train_state(cfg, OBS, ACT, cfg.seed)
    for c, rows in enumerate(_rows(2)):  # the second chunk starts at count K, not 0
        out = chunk(s_c, rows)
        s_c = out.state
        got = jax.device_get((s_c, out.td_errors, out.metrics))
        assert set(got[2]) == set(keys) and all(np.ndim(v) == 0 for v in got[2].values())
        tds, ms = [], []
        for k in range(K):
            one = single(s_1, unpack_batch(rows[k], OBS, ACT))
            s_1 = one.state
            tds.append(np.asarray(one.td_errors))
            ms.append(jax.device_get(one.metrics))
        metrics = {
            k: ms[-1][k] if k in learner_lib.LAST_UPDATE_KEYS else np.mean([m[k] for m in ms])
            for k in keys
        }
        jax.tree.map(
            lambda x, y: np.testing.assert_allclose(x, y, rtol=2e-4, atol=1e-5),
            got, jax.device_get((s_1, np.stack(tds), metrics)),
        )
        assert got[2]["critic_grad_norm"] > 0
        # the counts advance in the carry: the critics' on every update, the
        # actor's and the temperature's on the delay's (learner.delayed_updates)
        done = (c + 1) * K
        policy = int(learner_lib.delayed_updates(done, DELAYED.get(family, 1)))
        assert int(got[0].step) == int(got[0].critic_opt.count) == done
        assert int(got[0].actor_opt.count) == policy
        if cfg.sac:
            assert int(got[0].alpha_opt.count) == policy
