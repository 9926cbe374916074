"""REDQ (Chen, Wang, Zhou and Ross 2021, "Randomized Ensembled Double
Q-Learning: Learning Fast Without a Model", arXiv 2101.05982, Algorithm 1, on
soft actor-critic), one update in plain float32 `jax.numpy`. With theta the
actor, phi_1..N the critics and phi'_1..N their targets:

1. a~' is the tanh-Gaussian sample of pi_theta(.|s') from standard normals;
   a set of M distinct indices is drawn uniformly from the N critics;
   y = R + d * (min over the drawn i of Q_phi'_i(s', a~') - alpha * log
   pi_theta(a~'|s')).
2. Every critic takes an Adam step on its weighted squared error against that
   one y, and every target one Polyak step, on EVERY update.
3. On every `policy_delay`-th update (those whose step count before the
   update is 0, G, 2G, ...; G stands for the paper's update-to-data ratio as
   the structure of its loop) the actor takes an Adam step on mean_b(alpha *
   log pi(a~|s) - (1/N) sum_i Q_phi_i(s, a~)): the ensemble's MEAN, where SAC
   has the minimum, through the critics as they stood before this update;
   and log alpha one on -log alpha * (mean log pi + target entropy). On every
   other update the actor, the temperature, their Adam moments and step counts
   are handed on bit for bit.

All N target critics are evaluated here and the drawn ones selected
(`in_target_value`); the program gathers the drawn critics' weights and runs
M passes. A row is [obs | action | R | d | next_obs | w], d = gamma * (1 -
done) folded in by the replay. The critics are stacked on a leading axis of
N, each seeded on its own (`split(k_critic, N)`), as the program's state
holds them.

The randomness of update t, t the step count before the update, from key =
fold_in(PRNGKey(seed ^ 0x5AC0), t): the normals are `normal(split(key))`,
next-state draw first (SAC's stream), and the set is `choice(fold_in(key,
0x5B5E7), N, (M,), replace=False)`: the one random stream both sides must
share for the numbers to be comparable at all.

`td`, per sample and signed, is the mean over the N critics of y - Q_i(s, a).
`actor_loss` and `actor_grad_norm` read 0 on an update that skips the policy:
the program runs no pass through the N critics for its record there.
`q_spread`, per update, is the batch mean of the standard deviation over the
N critics' Q_i(s, a) (the program's `redq_q_spread`, which a chunk reports
for its last update).

Departures from the paper, all the program's, none of them a width (the nets
are as wide as the configuration's `hidden` says, and this file fixes none):
- SAC's, as reference/sac.py lists them: log_std squashed onto [-5, 2] by a
  tanh; the density in environment action units, so the target entropy is
  -dim(A) + sum(log scale), where the authors' code has a target per task;
  the action joins the critics at their second layer; the temperature's Adam
  at the critics' learning rate;
- the critic loss is the MEAN over the N critics' weighted squared errors, a
  N-th of the sum the paper writes, so each critic's gradient is a N-th of
  the paper's at the same learning rate (Adam divides most of that out);
- the loop is not the paper's: a decoupled learner free-runs, so G updates
  do not wait for one environment step; G is kept as one policy step in G
  updates, with the policy step on the first of each G (the paper: the last);
- the actors warm the ring with 1,000 rows of the policy's own sampling
  where the paper takes 5,000 uniformly random steps first (outside this
  update).
PAPERS.md holds what this tree knows of the paper's settings.
"""

import jax
import jax.numpy as jnp

from . import common as c
from .d4pg import products  # the rounding as lax.reduce_precision, no float8 array in the program

LOG_STD_MIN, LOG_STD_MAX = -5.0, 2.0
SUBSET_FOLD = 0x5B5E7


def init(seed, env, hp):
    k_actor, k_critic = jax.random.split(jax.random.PRNGKey(seed))
    actor = c.actor_init(k_actor, env["obs_dim"], 2 * env["act_dim"], hp["hidden"])
    members = [
        c.critic_init(k, env["obs_dim"], env["act_dim"], hp["hidden"])
        for k in jax.random.split(k_critic, hp["critic_ensemble"])
    ]
    critic = jax.tree.map(lambda *m: jnp.stack(m), *members)
    log_alpha = jnp.log(jnp.asarray(hp["alpha0"], jnp.float32))
    return {
        "actor": actor,
        "critic": critic,
        "target_critic": critic,
        "actor_opt": c.adam_init(actor),
        "critic_opt": c.adam_init(critic),
        "log_alpha": log_alpha,
        "alpha_opt": c.adam_init(log_alpha),
        "step": jnp.zeros((), jnp.int32),
        # carried in the state so that one compiled reference serves every seed
        "noise_key": jax.random.PRNGKey(seed ^ 0x5AC0),
    }


def work(env, hp):
    """Operations and bytes of one update (common.work), the algorithm's and
    averaged over the policy's period G. Actor (head 2 * act wide): forward
    on s' for the target on every update (1) + forward and backward on s on
    every G-th (3) = 1 + 3/G. Critics: each of the N a TD forward and
    backward on every update (3) and a forward and backward-to-the-action
    under the actor on every G-th (3); M of the N a target forward (1): 3 +
    M/N + 3/G for each."""
    n, m, g = float(hp["critic_ensemble"]), float(hp["target_subset"]), float(hp["policy_delay"])
    return c.work(
        env, hp, actor_out=2 * env["act_dim"], n_critics=hp["critic_ensemble"],
        actor_passes=1.0 + 3.0 / g, critic_passes=3.0 + m / n + 3.0 / g,
    )


def draws(key, t, hp, shape):
    """Update t's randomness: (normals at s', normals at s, the in-target
    set int32[M])."""
    key = jax.random.fold_in(key, t)
    k_next, k_cur = jax.random.split(key)
    subset = jax.random.choice(
        jax.random.fold_in(key, SUBSET_FOLD), hp["critic_ensemble"], (hp["target_subset"],), replace=False
    )
    return jax.random.normal(k_next, shape), jax.random.normal(k_cur, shape), subset


# Algorithm 1's three choices, each a function of its own so that a test can
# bend one and see the comparison fail (tests/test_reference_redq.py).


def in_target_value(next_q, subset):
    """[N, B] target values and the drawn set -> [B]: the minimum over the set."""
    return jnp.min(next_q[subset], axis=0)


def policy_value(q):
    """[N, B] online values at the policy's action -> [B]: the ensemble's mean."""
    return jnp.mean(q, axis=0)


def targets_move(policy_steps):
    """Whether the targets take their Polyak step on this update: always,
    whether or not the policy steps."""
    return True


def make_step(seed, env, hp, operand_dtype=None):
    mm = products(operand_dtype)
    act_dim = env["act_dim"]
    scale = jnp.broadcast_to(jnp.asarray(env["action_scale"], jnp.float32), (act_dim,))
    offset = jnp.asarray(env["action_offset"], jnp.float32)
    target_entropy = -float(act_dim) + float(jnp.sum(jnp.log(scale)))

    def sample(params, obs, eps):
        mean, raw = jnp.split(c.mlp_body(mm, params, obs), 2, axis=-1)
        log_std = LOG_STD_MIN + 0.5 * (LOG_STD_MAX - LOG_STD_MIN) * (jnp.tanh(raw) + 1.0)
        std = jnp.exp(log_std)
        u = mean + std * eps
        t = jnp.tanh(u)
        gauss = -0.5 * (jnp.square((u - mean) / std) + 2.0 * log_std + jnp.log(2.0 * jnp.pi))
        log_det = jnp.log(scale * (1.0 - jnp.square(t)) + 1e-6)
        return t * scale + offset, jnp.sum(gauss - log_det, axis=-1)

    def ensemble(params, obs, action):
        return jax.vmap(lambda p: c.critic_apply(mm, p, obs, action))(params)  # [N, B]

    def step(s, rows):
        b = c.unpack(rows, env["obs_dim"], act_dim)
        eps_next, eps_cur, subset = draws(s["noise_key"], s["step"], hp, b["action"].shape)
        alpha = jnp.exp(s["log_alpha"])
        next_a, next_lp = sample(s["actor"], b["next_obs"], eps_next)
        next_q = in_target_value(ensemble(s["target_critic"], b["next_obs"], next_a), subset)
        y = b["reward"] + b["discount"] * (next_q - alpha * next_lp)

        def critic_loss(cp):
            q = ensemble(cp, b["obs"], b["action"])
            td = y[None, :] - q
            return jnp.mean(b["weight"][None, :] * jnp.square(td)), (jnp.mean(td, axis=0), q)

        (closs, (td, q)), cgrad = jax.value_and_grad(critic_loss, has_aux=True)(s["critic"])

        def actor_loss(ap):
            a, lp = sample(ap, b["obs"], eps_cur)
            return jnp.mean(alpha * lp - policy_value(ensemble(s["critic"], b["obs"], a))), jnp.mean(lp)

        (aloss, mean_lp), agrad = jax.value_and_grad(actor_loss, has_aux=True)(s["actor"])
        critic, critic_opt = c.adam(s["critic"], cgrad, s["critic_opt"], hp["critic_lr"])
        actor, actor_opt = c.adam(s["actor"], agrad, s["actor_opt"], hp["actor_lr"])
        # J(alpha) = E[-alpha * (log pi + target entropy)], in log(alpha).
        log_alpha, alpha_opt = c.adam(
            s["log_alpha"], -(mean_lp + target_entropy), s["alpha_opt"], hp["critic_lr"]
        )
        policy = {"actor": actor, "actor_opt": actor_opt, "log_alpha": log_alpha, "alpha_opt": alpha_opt}
        policy_steps = s["step"] % hp["policy_delay"] == 0
        # a select, not arithmetic: a skipped update hands the old bits on
        new = jax.tree.map(lambda a, b: jnp.where(policy_steps, a, b), policy, {k: s[k] for k in policy})
        new["target_critic"] = jax.tree.map(
            lambda a, b: jnp.where(targets_move(policy_steps), a, b),
            c.polyak(critic, s["target_critic"], hp["tau"]), s["target_critic"],
        )
        new.update(critic=critic, critic_opt=critic_opt, step=s["step"] + 1, noise_key=s["noise_key"])
        return new, {
            "td": td,
            "critic_loss": closs,
            "actor_loss": jnp.where(policy_steps, aloss, 0.0),
            "critic_grad_norm": c.tree_norm(cgrad),
            "actor_grad_norm": jnp.where(policy_steps, c.tree_norm(agrad), 0.0),
            "q_spread": jnp.mean(jnp.std(q, axis=0)),
        }

    return step
