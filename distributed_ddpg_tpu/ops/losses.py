"""DDPG / D4PG losses (SURVEY.md §3.3; DDPG arXiv 1509.02971, D4PG arXiv 1804.08617).

- Critic: squared TD error against the bootstrapped target
  y = r + discount * Q'(s', mu'(s')), where `discount` already folds
  gamma^n * (1 - done) for n-step returns (types.Batch).
- Actor: deterministic policy gradient, implemented as the scalar loss
  -mean(Q(s, mu(s))) so `jax.grad` produces grad_theta mu(s) * grad_a Q.
- Distributional critic (D4PG): categorical projection of the target
  distribution onto a fixed support (C51-style), cross-entropy loss.

All functions are pure and shape-static so they trace once under jit.
PER importance weights enter as `batch.weight`; per-sample TD errors are
returned for host-side priority updates (SURVEY.md §2 #7).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributed_ddpg_tpu.models.mlp import actor_apply, critic_apply
from distributed_ddpg_tpu.types import Batch


def td_targets(batch: Batch, next_q):
    return batch.reward + batch.discount * next_q


def critic_loss(
    critic_params,
    target_actor_params,
    target_critic_params,
    batch: Batch,
    action_scale,
    action_insert_layer: int = 1,
    l2: float = 0.0,
    action_offset=0.0,
    mm_dtype=None,
):
    """Weighted MSE TD loss. Returns (loss, td_errors[B])."""
    next_action = actor_apply(
        target_actor_params, batch.next_obs, action_scale, action_offset, mm_dtype
    )
    next_q = critic_apply(
        target_critic_params, batch.next_obs, next_action, action_insert_layer, mm_dtype
    )
    y = jax.lax.stop_gradient(td_targets(batch, next_q))
    q = critic_apply(critic_params, batch.obs, batch.action, action_insert_layer, mm_dtype)
    td = y - q
    loss = jnp.mean(batch.weight * jnp.square(td))
    if l2 > 0.0:
        loss = loss + l2 * sum(
            jnp.sum(jnp.square(layer["w"])) for layer in critic_params
        )
    return loss, td


def actor_loss(
    actor_params,
    critic_params,
    batch: Batch,
    action_scale,
    action_insert_layer: int = 1,
    action_offset=0.0,
    mm_dtype=None,
):
    """DPG loss: ascend Q(s, mu(s))."""
    action = actor_apply(actor_params, batch.obs, action_scale, action_offset, mm_dtype)
    q = critic_apply(critic_params, batch.obs, action, action_insert_layer, mm_dtype)
    return -jnp.mean(q)


# ---------------------------------------------------------------------------
# Twin critic (TD3, arXiv 1802.09477)
# ---------------------------------------------------------------------------


def td3_critic_loss(
    critic_params,
    target_actor_params,
    target_critic_params,
    batch: Batch,
    action_scale,
    noise=None,
    action_insert_layer: int = 1,
    l2: float = 0.0,
    action_offset=0.0,
    mm_dtype=None,
):
    """Clipped double-Q TD loss: min-over-ensemble Bellman target with
    target-policy smoothing by `noise` (f32[B, act], already scaled and
    clipped: learner.step_noise; None smooths nothing). `critic_params`
    leaves carry a leading ensemble axis of 2 (learner.init_train_state
    stacks them); the apply is vmapped over it — one batched program on the MXU, not two
    sequential critics. Loss is the MEAN of the two critics' weighted
    MSEs (lr-invariant vs the sum the paper writes), plus `l2` weight
    decay over both ensemble members (matching critic_loss). Returns
    (loss, (td_proxy[B], twin_gap)) where the proxy is the ensemble-mean TD
    error (PER priorities) and twin_gap the batch mean of |Q'_1 - Q'_2| at
    the target action (the `td3_twin_gap` metric: how much the clipped
    minimum bites)."""
    next_action = actor_apply(
        target_actor_params, batch.next_obs, action_scale, action_offset, mm_dtype
    )
    if noise is not None:
        lo = action_offset - action_scale
        hi = action_offset + action_scale
        next_action = jnp.clip(next_action + noise, lo, hi)
    ensemble = lambda p, o, a: jax.vmap(
        lambda cp: critic_apply(cp, o, a, action_insert_layer, mm_dtype)
    )(p)
    next_q = ensemble(target_critic_params, batch.next_obs, next_action)  # [2, B]
    y = jax.lax.stop_gradient(td_targets(batch, jnp.min(next_q, axis=0)))
    q = ensemble(critic_params, batch.obs, batch.action)  # [2, B]
    td = y[None, :] - q
    loss = jnp.mean(batch.weight[None, :] * jnp.square(td))
    if l2 > 0.0:
        loss = loss + l2 * sum(
            jnp.sum(jnp.square(layer["w"])) for layer in critic_params
        )
    twin_gap = jnp.mean(jnp.abs(next_q[0] - next_q[1]))
    return loss, (jnp.mean(td, axis=0), twin_gap)


def td3_actor_loss(
    actor_params,
    critic_params,
    batch: Batch,
    action_scale,
    action_insert_layer: int = 1,
    action_offset=0.0,
    mm_dtype=None,
):
    """DPG loss through critic 0 only (the TD3 convention)."""
    action = actor_apply(actor_params, batch.obs, action_scale, action_offset, mm_dtype)
    q1 = critic_apply(
        jax.tree.map(lambda x: x[0], critic_params),
        batch.obs, action, action_insert_layer, mm_dtype,
    )
    return -jnp.mean(q1)


# ---------------------------------------------------------------------------
# SAC (arXiv 1801.01290 / 1812.05905)
# ---------------------------------------------------------------------------

_TANH_EPS = 1e-6


def sac_sample(mean, log_std, eps, action_scale, action_offset=0.0):
    """Reparameterized tanh-Gaussian sample mapped onto the action box, from
    the standard normals `eps` (mean's shape; the draw is the caller's).

    Returns (action[B, A], log_prob[B]). log_prob folds the standard tanh
    change-of-variables correction PLUS the box scaling's -log(scale) per
    dim (the policy density lives in environment action units, so the
    entropy target -act_dim means "one nat below a unit-box uniform per
    dim" regardless of the env's scale). Gradients flow through `mean` and
    `log_std` (reparameterization); callers stop-gradient where the
    pathwise term is unwanted."""
    std = jnp.exp(log_std)
    u = mean + std * eps
    tanh_u = jnp.tanh(u)
    action = tanh_u * action_scale + action_offset
    # N(u; mean, std) log-density, summed over action dims.
    gauss_lp = -0.5 * (
        jnp.square((u - mean) / std) + 2.0 * log_std + jnp.log(2.0 * jnp.pi)
    )
    # d(action)/d(u) = scale * (1 - tanh(u)^2); log|det| subtracts.
    squash = jnp.log(action_scale * (1.0 - jnp.square(tanh_u)) + _TANH_EPS)
    log_prob = jnp.sum(gauss_lp - squash, axis=-1)
    return action, log_prob


def sac_critic_loss(
    critic_params,
    actor_params,
    target_critic_params,
    batch: Batch,
    action_scale,
    eps,
    alpha,
    log_std_min: float,
    log_std_max: float,
    action_insert_layer: int = 1,
    l2: float = 0.0,
    action_offset=0.0,
    mm_dtype=None,
    subset=None,
    ensemble_stats: bool = False,
    resid_share: bool = False,
):
    """Entropy-regularized clipped double-Q TD loss:
    y = r + discount * (min_i Q'_i(s', a') - alpha * log pi(a'|s')),
    a' ~ pi(.|s') drawn from the CURRENT actor (SAC has no target actor)
    with the standard normals `eps` (f32[B, act]).
    `critic_params` leaves carry the leading ensemble axis of N (2 as
    TD3's, unless config.critic_ensemble says otherwise:
    learner.init_train_state). Returns (loss, td_proxy[B]) with the
    ensemble-mean TD error as the PER priority proxy.

    REDQ (`subset`, int32[M], distinct members of the ensemble): the minimum
    runs over the M drawn target critics only, whose leaves are gathered by
    index, so the target costs M forward passes and not N; every online
    critic regresses on that one y. With `ensemble_stats` the aux is
    (td_proxy, q_spread, mean_q): the batch mean of the standard deviation
    over the N online Q_i(s, a), and their mean, both of the q this loss
    holds anyway. Residual critics (`resid_share`; models/mlp.simba_apply):
    the aux is (td_proxy, the mean over critics, blocks and rows of
    |f(LN(x))| / |x + f(LN(x))| in the online pass at (s, a)). Their target
    critics normalise with their own statistics leaves, which polyak_update
    keeps equal to the online nets'."""
    from distributed_ddpg_tpu.models.mlp import actor_gaussian_apply

    mean, log_std = actor_gaussian_apply(
        actor_params, batch.next_obs, log_std_min, log_std_max, mm_dtype
    )
    next_action, next_lp = sac_sample(mean, log_std, eps, action_scale, action_offset)
    ensemble = lambda p, o, a: jax.vmap(
        lambda cp: critic_apply(cp, o, a, action_insert_layer, mm_dtype)
    )(p)
    if subset is not None:
        target_critic_params = jax.tree.map(
            lambda x: x[subset], target_critic_params
        )
    next_q = jnp.min(
        ensemble(target_critic_params, batch.next_obs, next_action), axis=0
    )
    y = jax.lax.stop_gradient(td_targets(batch, next_q - alpha * next_lp))
    if resid_share:
        q, share = jax.vmap(
            lambda cp: critic_apply(
                cp, batch.obs, batch.action, action_insert_layer, mm_dtype,
                resid=True,
            )
        )(critic_params)
    else:
        q = ensemble(critic_params, batch.obs, batch.action)  # [N, B]
    td = y[None, :] - q
    loss = jnp.mean(batch.weight[None, :] * jnp.square(td))
    if l2 > 0.0:
        # Weight decay over both ensemble members (matching td3_critic_loss).
        loss = loss + l2 * sum(
            jnp.sum(jnp.square(layer["w"])) for layer in critic_params
        )
    if ensemble_stats:
        return loss, (
            jnp.mean(td, axis=0), jnp.mean(jnp.std(q, axis=0)), jnp.mean(q)
        )
    if resid_share:
        return loss, (jnp.mean(td, axis=0), jax.lax.stop_gradient(jnp.mean(share)))
    return loss, jnp.mean(td, axis=0)


def crossq_critic_loss(
    critic_params,
    actor_params,
    batch: Batch,
    action_scale,
    eps,
    alpha,
    log_std_min: float,
    log_std_max: float,
    action_insert_layer: int = 1,
    l2: float = 0.0,
    action_offset=0.0,
    mm_dtype=None,
    axis_name=None,
):
    """CrossQ's critic loss (arXiv 1902.05605): sac_critic_loss without
    target networks. a' ~ pi(.|s') from the actor in evaluation mode; each
    batch-normalised critic runs ONCE, in training mode, on the joint batch
    [(s, a); (s', a')] (stacked on a leading axis of 2, so a data mesh
    shards both halves alike), whose 2B rows give the one set of batch
    moments that normalises both halves; the prediction is the first half,
    the Bellman target y = r + discount * stop_gradient(min_i q'_i - alpha *
    log pi(a'|s')) the second. Returns (loss, (td_proxy[B], mean_q,
    bn_stat_gap, moments)): the critics' mean Q(s, a), mlp.norm_stat_gap of
    the pass, and its moments for mlp.norm_moved, all of what this loss
    holds anyway."""
    from distributed_ddpg_tpu.models.mlp import actor_gaussian_apply, norm_stat_gap

    mean, log_std = actor_gaussian_apply(
        actor_params, batch.next_obs, log_std_min, log_std_max, mm_dtype
    )
    next_action, next_lp = sac_sample(mean, log_std, eps, action_scale, action_offset)
    obs = jnp.stack([batch.obs, batch.next_obs])
    action = jnp.stack([batch.action, next_action])
    joint, moments = jax.vmap(
        lambda cp: critic_apply(
            cp, obs, action, action_insert_layer, mm_dtype,
            train=True, axis_name=axis_name,
        )
    )(critic_params)  # [N, 2, B]
    q, next_q = joint[:, 0], joint[:, 1]
    y = jax.lax.stop_gradient(
        td_targets(batch, jnp.min(next_q, axis=0) - alpha * next_lp)
    )
    td = y[None, :] - q
    loss = jnp.mean(batch.weight[None, :] * jnp.square(td))
    if l2 > 0.0:
        loss = loss + l2 * sum(
            jnp.sum(jnp.square(layer["w"])) for layer in critic_params
        )
    return loss, (
        jnp.mean(td, axis=0), jnp.mean(q),
        norm_stat_gap(critic_params, moments), moments,
    )


def sac_actor_loss(
    actor_params,
    critic_params,
    batch: Batch,
    action_scale,
    eps,
    alpha,
    log_std_min: float,
    log_std_max: float,
    action_insert_layer: int = 1,
    action_offset=0.0,
    mm_dtype=None,
    reduce=jnp.min,
    train_norm: bool = False,
    axis_name=None,
):
    """Reparameterized actor objective E[alpha * log pi(a|s) - min_i Q_i(s, a)],
    a drawn with the standard normals `eps` (f32[B, act]).

    Unlike TD3 (critic 0 only), SAC minimizes against the ensemble MIN —
    the 1812.05905 convention; REDQ, whose target draws a subset, against
    the ensemble MEAN (`reduce=jnp.mean`; 2101.05982, Algorithm 1).
    Returns (loss, mean_log_prob) — the aux feeds the alpha (temperature)
    update. CrossQ (`train_norm`): the batch-normalised actor runs in
    training mode on its own B rows, the critics under it in evaluation
    mode (running statistics), and the aux is (mean_log_prob, the actor's
    moments for mlp.norm_moved)."""
    from distributed_ddpg_tpu.models.mlp import actor_gaussian_apply

    head = actor_gaussian_apply(
        actor_params, batch.obs, log_std_min, log_std_max, mm_dtype,
        train=train_norm, axis_name=axis_name,
    )
    (mean, log_std), moments = head if train_norm else (head, None)
    action, lp = sac_sample(mean, log_std, eps, action_scale, action_offset)
    q = reduce(
        jax.vmap(
            lambda cp: critic_apply(cp, batch.obs, action, action_insert_layer, mm_dtype)
        )(critic_params),
        axis=0,
    )
    if train_norm:
        return jnp.mean(alpha * lp - q), (jnp.mean(lp), moments)
    return jnp.mean(alpha * lp - q), jnp.mean(lp)


def sac_target_entropy(
    target_entropy: float, act_dim: int, action_scale, scale: float = 1.0,
):
    """Resolve the temperature target as a trace-time Python float (jnp
    here would yield a tracer under jit): an explicit `target_entropy`
    wins; nan (the config sentinel) means auto — the 1812.05905 -act_dim
    heuristic (times `scale`, config.target_entropy_scale: SimBa's 1/2),
    which is stated for UNIT-box log-probs, shifted by
    +sum(log scale) because sac_sample's densities live in env action
    units (without the shift any env with scale > 1 gets a LOWER-entropy
    target than standard SAC and alpha collapses — measured on Pendulum,
    scale 2: alpha -> 0.017 and stuck). Shared by learner.sac_step and
    the fused kernel wrapper so the two paths cannot desync."""
    import math

    import numpy as np

    if not math.isnan(target_entropy):
        return float(target_entropy)
    return -scale * float(act_dim) + float(
        np.sum(
            np.log(
                np.broadcast_to(
                    np.asarray(action_scale, np.float64), (act_dim,)
                )
            )
        )
    )


# ---------------------------------------------------------------------------
# Distributional critic (D4PG)
# ---------------------------------------------------------------------------


def categorical_support(v_min: float, v_max: float, num_atoms: int):
    return jnp.linspace(v_min, v_max, num_atoms)


def categorical_projection(support, target_probs, rewards, discounts):
    """Project the shifted/scaled target distribution back onto `support`.

    support: f32[A]; target_probs: f32[B, A]; rewards, discounts: f32[B].
    Returns f32[B, A], float32 throughout. Standard C51 projection: source
    atom a's mass goes to the two atoms round its Bellman-updated position.

    The mass is placed by comparison, not by indexing: a source atom's two
    destinations are the masks `index == arange(A)`, and the masked masses
    are summed over the source atoms. That is elementwise work and a
    reduction over [B, A, A], which the TPU's compiler keeps inside two loop
    fusions that read [B, A] operands: 1.0 us an update at [256, 51] in the
    DMPO cell's scan body. Indexing a one-hot table (`jnp.eye(A)[lo]`, this
    function until PR 52) compiled there to two gathers of B*A rows out of
    the A x A table, 17 us each, with a copy (4.5 us) and a reshape (14 us)
    of the pred[B*A, A] block behind each and a sum that read the block back:
    81.7 of that update's 213 us (PERF.md §5, §6, PR 52). No `dot_general`
    either: a dot of float32 operands runs in one bfloat16 pass on the TPU
    and would round the probabilities to eight bits.
    """
    v_min, v_max = support[0], support[-1]
    num_atoms = support.shape[0]
    dz = (v_max - v_min) / (num_atoms - 1)
    # Bellman-updated atom positions, clipped to the support: f32[B, A]
    tz = jnp.clip(
        rewards[:, None] + discounts[:, None] * support[None, :], v_min, v_max
    )
    b = (tz - v_min) / dz                 # fractional index in [0, A-1]
    lower = jnp.floor(b)
    upper = jnp.ceil(b)
    # When b lands exactly on an atom, put all mass on it (lower == upper).
    eq = (upper == lower).astype(target_probs.dtype)
    w_lower = (upper - b) + eq            # mass to the lower atom
    w_upper = b - lower
    # [B, A source, A destination] masks. The division can leave b a rounding
    # above A-1 and its ceil at A: the top atom's, as the gather clamped it.
    atoms = jnp.arange(num_atoms, dtype=jnp.int32)
    lo = lower.astype(jnp.int32)[:, :, None] == atoms
    up = jnp.minimum(upper.astype(jnp.int32), num_atoms - 1)[:, :, None] == atoms
    proj = jnp.where(lo, (target_probs * w_lower)[:, :, None], 0.0).sum(axis=1)
    return proj + jnp.where(up, (target_probs * w_upper)[:, :, None], 0.0).sum(axis=1)


def distributional_critic_loss(
    critic_params,
    target_actor_params,
    target_critic_params,
    batch: Batch,
    action_scale,
    support,
    action_insert_layer: int = 1,
    action_offset=0.0,
    mm_dtype=None,
):
    """Categorical TD loss (cross-entropy vs projected target distribution).

    Returns (loss, (td_error_proxy[B], edge_mass)): the proxy is the signed
    E[Z_target] - E[Z] (PER priorities, as in D4PG follow-ups); edge_mass is
    the batch-mean share of the projected target's mass on the support's two
    end atoms (the `c51_edge_mass` metric: how much of the return
    distribution v_min / v_max clip)."""
    next_action = actor_apply(
        target_actor_params, batch.next_obs, action_scale, action_offset, mm_dtype
    )
    target_logits = critic_apply(
        target_critic_params, batch.next_obs, next_action, action_insert_layer, mm_dtype
    )
    target_probs = jax.nn.softmax(target_logits, axis=-1)
    proj = jax.lax.stop_gradient(
        categorical_projection(support, target_probs, batch.reward, batch.discount)
    )
    logits = critic_apply(
        critic_params, batch.obs, batch.action, action_insert_layer, mm_dtype
    )
    logprobs = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.sum(proj * logprobs, axis=-1)
    loss = jnp.mean(batch.weight * ce)
    mean_q = jnp.sum(jax.nn.softmax(logits, axis=-1) * support[None, :], axis=-1)
    mean_target = jnp.sum(proj * support[None, :], axis=-1)
    edge_mass = jnp.mean(proj[:, 0] + proj[:, -1])
    return loss, (mean_target - mean_q, edge_mass)


def distributional_actor_loss(
    actor_params,
    critic_params,
    batch: Batch,
    action_scale,
    support,
    action_insert_layer: int = 1,
    action_offset=0.0,
    mm_dtype=None,
):
    action = actor_apply(actor_params, batch.obs, action_scale, action_offset, mm_dtype)
    logits = critic_apply(critic_params, batch.obs, action, action_insert_layer, mm_dtype)
    q = jnp.sum(jax.nn.softmax(logits, axis=-1) * support[None, :], axis=-1)
    return -jnp.mean(q)


# ---------------------------------------------------------------------------
# MPO on the categorical critic (DMPO: Acme, arXiv 2006.00979, agents/tf/dmpo
# with tf/losses/mpo.py; the policy step arXiv 1806.06920, decoupled as in
# 1812.02256)
# ---------------------------------------------------------------------------
# Actions here are CANONICAL: the policy's Gaussian lives on [-1, 1]^A, a
# draw may leave the box, the critic reads it clipped, and the step maps the
# ring's environment-unit actions in before it calls these.

MPO_FLOAT_EPS = 1e-8
MPO_MIN_LOG_DUAL = -18.0  # the source clips every dual variable from below
MPO_DUALS = (
    "log_temperature", "log_penalty_temperature",
    "log_alpha_mean", "log_alpha_stddev",
)


def mpo_dual_values(duals):
    """Each dual variable out of log space: softplus(x) + 1e-8."""
    return jax.tree.map(lambda x: jax.nn.softplus(x) + MPO_FLOAT_EPS, duals)


def mpo_estep(
    target_actor_params, target_critic_params, next_obs, eps, support,
    mm_dtype=None,
):
    """The E-step's forward passes, no gradient: N actions a row drawn from
    the TARGET policy at s' with the standard normals `eps` f32[B, N, A],
    and the TARGET critic on all B * N (s', clipped action) rows. Returns
    (actions f32[B, N, A], unclipped; (mean', scale') of the target policy;
    probs f32[B, N, atoms]; q f32[B, N], each distribution's expectation)."""
    from distributed_ddpg_tpu.models.mlp import gaussian_apply

    mean_t, scale_t = gaussian_apply(target_actor_params, next_obs, mm_dtype)
    actions = mean_t[:, None, :] + scale_t[:, None, :] * eps
    b, n, a = actions.shape
    logits = critic_apply(
        target_critic_params,
        jnp.repeat(next_obs, n, axis=0),
        jnp.clip(actions, -1.0, 1.0).reshape(b * n, a),
        0, mm_dtype,
    )
    probs = jax.nn.softmax(logits, axis=-1).reshape(b, n, -1)
    return actions, (mean_t, scale_t), probs, jnp.sum(probs * support, axis=-1)


def mpo_out_of_box_cost(actions):
    """The action penalty's value of each drawn action: minus its distance
    from the box, 0 inside."""
    return -jnp.linalg.norm(actions - jnp.clip(actions, -1.0, 1.0), axis=-1)


def mpo_weights(values, temperature):
    """softmax over a row's N samples of values / temperature (f32[1]):
    f32[B, N]."""
    return jax.nn.softmax(values / temperature, axis=1)


def mpo_temperature_loss(values, epsilon: float, temperature):
    """The dual of the E-step's KL bound: eta * (epsilon + mean_B
    logsumexp_j(values / eta) - log N), eta f32[1]."""
    lse = jax.nn.logsumexp(values / temperature, axis=1)
    return jnp.sum(
        temperature * (epsilon + jnp.mean(lse) - jnp.log(values.shape[1]))
    )


def mpo_critic_loss(
    critic_params, batch: Batch, action, target_probs, support, mm_dtype=None,
):
    """distributional_critic_loss against the MIXTURE of the samples'
    distributions, `target_probs` f32[B, atoms], on (s, `action`): returns
    (loss, (td_error_proxy[B], edge_mass)) as it does."""
    proj = jax.lax.stop_gradient(
        categorical_projection(support, target_probs, batch.reward, batch.discount)
    )
    logits = critic_apply(critic_params, batch.obs, action, 0, mm_dtype)
    ce = -jnp.sum(proj * jax.nn.log_softmax(logits, axis=-1), axis=-1)
    mean_q = jnp.sum(jax.nn.softmax(logits, axis=-1) * support[None, :], axis=-1)
    mean_target = jnp.sum(proj * support[None, :], axis=-1)
    return jnp.mean(batch.weight * ce), (
        mean_target - mean_q, jnp.mean(proj[:, 0] + proj[:, -1])
    )


def _gaussian_log_prob(actions, mean, scale):
    """log N(a_j; mean, scale) summed over the action's dimensions:
    actions f32[B, N, A], mean and scale f32[B, A] -> f32[B, N]."""
    z = (actions - mean[:, None, :]) / scale[:, None, :]
    return jnp.sum(
        -0.5 * jnp.square(z) - jnp.log(scale)[:, None, :]
        - 0.5 * jnp.log(2.0 * jnp.pi),
        axis=-1,
    )


def mpo_kls(mean, scale, mean_t, scale_t):
    """The decoupled M-step's two KLs from the target policy, each the
    batch mean per action dimension, f32[A]: KL(N(mu', s') || N(mu, s')),
    which only the mean moves, and KL(N(mu', s') || N(mu', s)), which only
    the scale does."""
    kl_mean = 0.5 * jnp.square((mean_t - mean) / scale_t)
    kl_std = (
        jnp.log(scale / scale_t)
        + 0.5 * jnp.square(scale_t / scale) - 0.5
    )
    return jnp.mean(kl_mean, axis=0), jnp.mean(kl_std, axis=0)


def mpo_policy_loss(
    mean, scale, mean_t, scale_t, actions, weights, alpha_mean, alpha_stddev,
):
    """The decoupled M-step on the online head's (mean, scale): weighted
    maximum likelihood of the drawn actions under N(mean, scale') and under
    N(mean', scale), plus each KL times its multiplier (held fixed here:
    the dual loss moves it). Returns (loss, (kl_mean[A], kl_std[A]))."""
    weights = jax.lax.stop_gradient(weights)
    fit_mean = -jnp.mean(
        jnp.sum(weights * _gaussian_log_prob(actions, mean, scale_t), axis=1)
    )
    fit_std = -jnp.mean(
        jnp.sum(weights * _gaussian_log_prob(actions, mean_t, scale), axis=1)
    )
    kl_mean, kl_std = mpo_kls(mean, scale, mean_t, scale_t)
    penalty = jnp.sum(jax.lax.stop_gradient(alpha_mean) * kl_mean) + jnp.sum(
        jax.lax.stop_gradient(alpha_stddev) * kl_std
    )
    return fit_mean + fit_std + penalty, (kl_mean, kl_std)


def mpo_dual_loss(
    duals, q, cost, kl_mean, kl_std, epsilon: float, epsilon_penalty: float,
    epsilon_mean: float, epsilon_stddev: float,
):
    """What the four dual variables descend: both temperatures' losses and
    sum_dim alpha * (epsilon - KL) for the mean's and the scale's bound,
    values and KLs held fixed."""
    d = mpo_dual_values(duals)
    q, cost, kl_mean, kl_std = jax.lax.stop_gradient((q, cost, kl_mean, kl_std))
    return (
        mpo_temperature_loss(q, epsilon, d["log_temperature"])
        + mpo_temperature_loss(cost, epsilon_penalty, d["log_penalty_temperature"])
        + jnp.sum(d["log_alpha_mean"] * (epsilon_mean - kl_mean))
        + jnp.sum(d["log_alpha_stddev"] * (epsilon_stddev - kl_std))
    )
