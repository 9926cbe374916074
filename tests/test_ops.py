"""Unit tests for the pure ops (SURVEY.md §4 'Unit' row): Polyak = exact
lerp, Adam vs optax oracle, losses vs hand-computed closed forms, OU noise
mean-reversion statistics, action squashing at bounds."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_ddpg_tpu.config import DDPGConfig
from distributed_ddpg_tpu.models.mlp import (
    actor_apply,
    actor_init,
    critic_apply,
    critic_init,
)
from distributed_ddpg_tpu.ops import losses
from distributed_ddpg_tpu.ops.noise import OUNoise
from distributed_ddpg_tpu.ops.optim import adam_update
from distributed_ddpg_tpu.ops.polyak import polyak_update
from distributed_ddpg_tpu.types import Batch, OptState


def test_polyak_is_exact_lerp():
    online = {"w": jnp.ones((3,)) * 2.0}
    target = {"w": jnp.zeros((3,))}
    out = polyak_update(online, target, tau=0.25)
    np.testing.assert_allclose(out["w"], 0.5 * jnp.ones(3))


def test_adam_matches_optax():
    params = {"w": jnp.array([1.0, -2.0, 3.0]), "b": jnp.array([0.5])}
    opt = OptState(
        mu=jax.tree.map(jnp.zeros_like, params),
        nu=jax.tree.map(jnp.zeros_like, params),
        count=jnp.zeros((), jnp.int32),
    )
    ox = optax.adam(1e-3)
    ox_state = ox.init(params)
    p_mine, p_ox = params, params
    for i in range(5):
        grads = jax.tree.map(lambda x: jnp.sin(x + i), p_ox)
        p_mine, opt = adam_update(p_mine, jax.tree.map(lambda x: jnp.sin(x + i), p_mine), opt, 1e-3)
        updates, ox_state = ox.update(grads, ox_state, p_ox)
        p_ox = optax.apply_updates(p_ox, updates)
    for k in params:
        np.testing.assert_allclose(p_mine[k], p_ox[k], rtol=1e-6, atol=1e-7)


def test_critic_loss_closed_form():
    """On a linear critic with known weights the TD loss has a closed form."""
    # 1-layer critic (action inserted at layer 0): Q = [s, a] @ w + b
    params = ({"w": jnp.array([[1.0], [2.0]]), "b": jnp.array([0.5])},)
    tparams = params
    # target actor: single layer mapping s -> a, tanh-squashed
    aparams = ({"w": jnp.array([[0.0]]), "b": jnp.array([0.0])},)
    batch = Batch(
        obs=jnp.array([[1.0]]),
        action=jnp.array([[2.0]]),
        reward=jnp.array([1.0]),
        discount=jnp.array([0.9]),
        next_obs=jnp.array([[0.0]]),
        weight=jnp.array([1.0]),
    )
    # mu'(s') = tanh(0) = 0; Q'(s'=0, a=0) = 0.5 → y = 1 + 0.9*0.5 = 1.45
    # Q(s,a) = 1*1 + 2*2 + 0.5 = 5.5 → td = -4.05, loss = 16.4025
    loss, td = losses.critic_loss(
        params, aparams, tparams, batch, action_scale=1.0, action_insert_layer=0
    )
    np.testing.assert_allclose(float(loss), 4.05**2, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(td), [-4.05], rtol=1e-6)


def test_actor_loss_is_negative_mean_q():
    key = jax.random.PRNGKey(0)
    ap = actor_init(key, 3, 2, (16,))
    cp = critic_init(key, 3, 2, (16,), action_insert_layer=1)
    obs = jax.random.normal(key, (8, 3))
    batch = Batch(obs=obs, action=None, reward=None, discount=None, next_obs=None, weight=None)
    loss = losses.actor_loss(ap, cp, batch, action_scale=1.0)
    a = actor_apply(ap, obs, 1.0)
    q = critic_apply(cp, obs, a, 1)
    np.testing.assert_allclose(float(loss), -float(jnp.mean(q)), rtol=1e-6)


def test_action_squashing_at_bounds():
    """Saturated pre-activations must squash exactly to ±action_scale."""
    params = (
        {"w": jnp.full((1, 1), 100.0), "b": jnp.zeros((1,))},
    )
    out_hi = actor_apply(params, jnp.array([[1.0]]), action_scale=2.0)
    out_lo = actor_apply(params, jnp.array([[-1.0]]), action_scale=2.0)
    np.testing.assert_allclose(out_hi, [[2.0]], atol=1e-5)
    np.testing.assert_allclose(out_lo, [[-2.0]], atol=1e-5)


def test_ou_noise_mean_reversion():
    """Long-run OU statistics: mean ~ mu, std ~ sigma*sqrt(dt/(2*theta*dt - theta^2*dt^2))
    ~ sigma/sqrt(2*theta) for small dt. Check mean reversion + bounded std."""
    ou = OUNoise((1,), theta=0.15, sigma=0.2, dt=1.0, seed=0)
    samples = np.array([ou() for _ in range(20000)])
    # Discrete-time OU: x_{t+1} = (1-theta)x_t + sigma*N → var = sigma²/(1-(1-theta)²)
    expected_std = 0.2 / np.sqrt(1 - (1 - 0.15) ** 2)
    assert abs(samples[5000:].mean()) < 0.05
    np.testing.assert_allclose(samples[5000:].std(), expected_std, rtol=0.1)
    ou.reset()
    np.testing.assert_allclose(ou.state, 0.0)


def test_categorical_projection_identity():
    """With reward=0, discount=1 the projection is the identity."""
    support = losses.categorical_support(-1.0, 1.0, 5)
    probs = jnp.array([[0.1, 0.2, 0.4, 0.2, 0.1]])
    out = losses.categorical_projection(
        support, probs, jnp.array([0.0]), jnp.array([1.0])
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(probs), atol=1e-6)


def test_categorical_projection_terminal_delta():
    """Terminal transition (discount=0) projects all mass onto reward atom."""
    support = losses.categorical_support(-1.0, 1.0, 5)  # atoms at -1,-.5,0,.5,1
    probs = jnp.full((1, 5), 0.2)
    out = losses.categorical_projection(
        support, probs, jnp.array([0.5]), jnp.array([0.0])
    )
    np.testing.assert_allclose(np.asarray(out)[0], [0, 0, 0, 1.0, 0], atol=1e-6)
    # Off-atom reward splits mass linearly between neighbors.
    out = losses.categorical_projection(
        support, probs, jnp.array([0.25]), jnp.array([0.0])
    )
    np.testing.assert_allclose(np.asarray(out)[0], [0, 0, 0.5, 0.5, 0], atol=1e-6)


def test_projection_mass_conserved():
    key = jax.random.PRNGKey(1)
    support = losses.categorical_support(-10.0, 10.0, 51)
    logits = jax.random.normal(key, (32, 51))
    probs = jax.nn.softmax(logits, -1)
    r = jax.random.uniform(key, (32,), minval=-5, maxval=5)
    d = jax.random.uniform(key, (32,), minval=0, maxval=1)
    out = losses.categorical_projection(support, probs, r, d)
    np.testing.assert_allclose(np.asarray(out).sum(-1), 1.0, rtol=1e-5)


def _projection_by_gather(support, target_probs, rewards, discounts):
    """`losses.categorical_projection` as it stood until PR 52, the one-hots
    indexed out of an A x A table: the oracle of the comparison form."""
    v_min, v_max = support[0], support[-1]
    num_atoms = support.shape[0]
    dz = (v_max - v_min) / (num_atoms - 1)
    tz = jnp.clip(rewards[:, None] + discounts[:, None] * support[None, :], v_min, v_max)
    b = (tz - v_min) / dz
    lower, upper = jnp.floor(b), jnp.ceil(b)
    eq = (upper == lower).astype(target_probs.dtype)
    w_lower, w_upper = (upper - b) + eq, b - lower
    onehot = jnp.eye(num_atoms, dtype=target_probs.dtype)
    proj = jnp.einsum("ba,ba,baj->bj", target_probs, w_lower, onehot[lower.astype(jnp.int32)])
    return proj + jnp.einsum("ba,ba,baj->bj", target_probs, w_upper, onehot[upper.astype(jnp.int32)])


# case -> [(reward in atom distances, discount)] on the support [-150, 150]:
# dz is 6 at 51 atoms and 75 at 5, so a whole number of distances under a
# discount of 0 or 1 lands every source atom on an atom to the bit.
_PROJECTION_ROWS = {
    "on_an_atom": [(0, 1.0), (2, 1.0), (-1, 1.0), (1, 0.0), (0, 0.0)],  # the `eq` rule; (0, 1) is the identity
    "between_atoms": [(0.25, 1.0), (-1.6, 0.99 ** 5), (0.5, 0.5), (1 / 3, 0.99)],
    "clipped_at_v_max": [(1e3, 1.0), (60, 0.99), (0.7, 1.0)],  # the last clips its upper atoms only
    "clipped_at_v_min": [(-1e3, 1.0), (-60, 0.99), (-0.7, 1.0)],
    "terminal_row": [(0.3, 0.0), (-0.5, 0.0), (1e3, 0.0), (-1e3, 0.0)],
}


@pytest.mark.parametrize("num_atoms", [51, 5])
@pytest.mark.parametrize("case", sorted(_PROJECTION_ROWS))
def test_categorical_projection_places_the_mass_where_the_gather_form_did(case, num_atoms):
    """The comparison form against the parent's on the same float32 weights:
    apart by the order of an A-term sum at most, every row a distribution."""
    support = losses.categorical_support(-150.0, 150.0, num_atoms)
    dz = 300.0 / (num_atoms - 1)
    rows = _PROJECTION_ROWS[case] * 3  # each row under three draws of the probabilities
    rewards = jnp.asarray([r * dz for r, _ in rows], jnp.float32)
    discounts = jnp.asarray([d for _, d in rows], jnp.float32)
    rng = np.random.default_rng(num_atoms)
    probs = jax.nn.softmax(jnp.asarray(2.0 * rng.normal(size=(len(rows), num_atoms)), jnp.float32))
    ours = jax.jit(losses.categorical_projection)(support, probs, rewards, discounts)
    assert ours.dtype == jnp.float32 and ours.shape == probs.shape
    np.testing.assert_allclose(ours, _projection_by_gather(support, probs, rewards, discounts), atol=1e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(ours).sum(-1), 1.0, atol=1e-6, rtol=0)
    assert float(ours.min()) >= 0.0
    if case == "on_an_atom":
        np.testing.assert_allclose(ours[0], probs[0], atol=1e-6, rtol=0)  # reward 0, discount 1
        assert np.count_nonzero(np.asarray(ours[3])) == 1  # a terminal return on an atom: one atom holds it all
    if case.startswith("clipped"):
        end = -1 if case.endswith("max") else 0
        np.testing.assert_allclose(ours[:2, end], 1.0, atol=1e-6, rtol=0)


def test_categorical_projection_keeps_the_mass_of_an_index_rounded_past_the_top():
    """On [0, 4.1] with 51 atoms float32's (v_max - v_min) / dz reads
    50.000004, whose ceil is 51: the gather clamped that index to the top
    atom, and the comparison form does the same and drops nothing."""
    support = losses.categorical_support(0.0, 4.1, 51)
    assert float((support[-1] - support[0]) / ((support[-1] - support[0]) / 50)) > 50.0
    probs = jax.nn.softmax(jnp.asarray(np.random.default_rng(0).normal(size=(4, 51)), jnp.float32))
    rewards, discounts = jnp.asarray([0.0, 9.0, 4.1, 0.05]), jnp.asarray([1.0, 1.0, 0.0, 0.99])
    ours = losses.categorical_projection(support, probs, rewards, discounts)
    np.testing.assert_allclose(ours, _projection_by_gather(support, probs, rewards, discounts), atol=1e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(ours).sum(-1), 1.0, atol=1e-6, rtol=0)


def _primitives(jaxpr):
    """The names of a jaxpr's primitives, through every jaxpr it closes over."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for param in eqn.params.values():
            for inner in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


def test_categorical_projection_traces_to_no_gather_and_no_dot():
    """The form: compare, select and reduce_sum. A gather of the one-hot
    table runs on the TPU as an operation of its own with a relayout behind
    it (PERF.md §6, PR 52), and a dot would multiply float32 probabilities in
    one bfloat16 pass there. The oracle above is the counter-example."""
    args = (jnp.linspace(-150.0, 150.0, 51), jnp.full((256, 51), 1 / 51), jnp.zeros(256), jnp.ones(256))
    found = set(_primitives(jax.make_jaxpr(losses.categorical_projection)(*args).jaxpr))
    assert {"eq", "select_n", "reduce_sum"} <= found
    assert not found & {"gather", "dot_general", "scatter", "scatter-add"}
    assert {"gather", "dot_general"} <= set(_primitives(jax.make_jaxpr(_projection_by_gather)(*args).jaxpr))


def test_actor_offset_for_asymmetric_spaces():
    """tanh output must map onto [low, high] when the box is asymmetric."""
    params = ({"w": jnp.full((1, 1), 100.0), "b": jnp.zeros((1,))},)
    # Box [0, 1]: scale 0.5, offset 0.5.
    hi = actor_apply(params, jnp.array([[1.0]]), action_scale=0.5, action_offset=0.5)
    lo = actor_apply(params, jnp.array([[-1.0]]), action_scale=0.5, action_offset=0.5)
    np.testing.assert_allclose(hi, [[1.0]], atol=1e-5)
    np.testing.assert_allclose(lo, [[0.0]], atol=1e-5)


def test_action_insert_layer_validation():
    with pytest.raises(ValueError):
        critic_init(jax.random.PRNGKey(0), 3, 2, (16, 16), action_insert_layer=3)
    with pytest.raises(ValueError):
        DDPGConfig(critic_hidden=(16, 16), action_insert_layer=5)
