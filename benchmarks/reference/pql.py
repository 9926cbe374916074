"""PQL's learner (Li, Chen, Hong, Ajay and Agrawal 2023, "Parallel Q-Learning",
arXiv 2307.12983): DDPG with double Q and n-step returns, one update in plain
float32 `jax.numpy` on rows the actors have already folded.

A row is [obs | action | R | d | next_obs | w]: R the return over the m <= n
steps folded before the episode's end, d = gamma^m, or 0 where the last folded
step truly terminated, next_obs the observation m steps on. d is READ from the
row and never recomputed from gamma: a row cut short at an episode's end
carries its own. With theta the actor, phi_1 and phi_2 the critics (stacked on
a leading axis of 2, as the program's state holds them) and primes the
targets, one update is

1. y = R + d * min_i Q_phi'i(next_obs, mu_theta'(next_obs)), no smoothing noise;
2. critic loss mean_i mean_b w * (Q_phi_i(obs, action) - y)^2, and phi_1, phi_2
   take an Adam step on every update;
3. on every `policy_delay`-th update (those whose step count before the update
   is 0, d, 2d, ...): actor loss -mean_b Q_phi_1(obs, mu_theta(obs)) through
   critic 1 as it stood before this update, theta takes an Adam step, and all
   three targets one Polyak step at tau; on every other update the actor, its
   Adam moments and step count, and every target are handed on bit for bit.

The action joins the critics at their input. `td`, per sample and signed, is
what the program reports as its TD errors on this branch: the mean over the
two critics of y - Q_i(obs, action). `actor_loss` on an update that skips the
actor is the forward value -mean Q_1(obs, mu(obs)), which the program still
computes for its record; `actor_grad_norm` reads 0 there.

Departures from the source, all the program's, none of them a width (the nets
are as wide as the configuration's `hidden` says, and this file fixes none):
- the critic loss is the MEAN of the two critics' weighted squared errors,
  half the sum the source writes (Adam divides most of that out);
- the targets take their Polyak step on the actor's beat, one update in
  `policy_delay`, where the source's critic process moves its targets on
  every critic update: the program's twin branch has one rule for the delay
  (`learner.delayed_updates`), TD3's;
- no observation normaliser: the source keeps a running mean and variance of
  the observations in front of every net, the program has none;
- the source runs actor, policy learner and critic learner as three processes
  on their own devices at the ratios a:v 1:8 and p:v 1:2; here one chip runs
  them in turn at the same ratios (the rollout between launches, the policy's
  half inside the update under a `cond`).
PAPERS.md holds what this tree knows of the source's settings.
"""

import jax
import jax.numpy as jnp

from . import common as c
from .d4pg import products  # the rounding as lax.reduce_precision


def critic_init(key, obs_dim, act_dim, hidden):
    """Q(s, a) with the action joining at the input."""
    dims = [obs_dim + act_dim, *hidden, 1]
    keys = jax.random.split(key, len(dims) - 1)
    return tuple(
        c.linear_init(keys[i], dims[i], dims[i + 1], i == len(dims) - 2)
        for i in range(len(dims) - 1)
    )


def init(seed, env, hp):
    k_actor, k_critic = jax.random.split(jax.random.PRNGKey(seed))
    actor = c.actor_init(k_actor, env["obs_dim"], env["act_dim"], hp["hidden"])
    critic = jax.tree.map(
        lambda a, b: jnp.stack([a, b]),
        *(critic_init(k, env["obs_dim"], env["act_dim"], hp["hidden"]) for k in jax.random.split(k_critic)),
    )
    return {
        "actor": actor,
        "critic": critic,
        "target_actor": actor,
        "target_critic": critic,
        "actor_opt": c.adam_init(actor),
        "critic_opt": c.adam_init(critic),
        "step": jnp.zeros((), jnp.int32),
    }


def work(env, hp):
    """{"flops", "row_bytes", "state_bytes"} of one update: what the algorithm
    needs, each once, the policy's half at 1 / `policy_delay` (common.py's
    conventions: matmul operations only, 2 * rows * in * out a product; the
    state read and written once a launch). With S(net) the sum of in * out
    over a net's layers and S'(net) that sum without the first layer (no
    gradient is needed with respect to a net's input rows):
    - every update: the target actor's forward on next_obs (S_a); each of the
      two target critics' forward (S_c); each critic's forward, weight
      gradients and input gradients behind the first layer (2 S_c + S'_c);
    - every `policy_delay`-th: the actor's forward, weight gradients and input
      gradients behind its first layer (2 S_a + S'_a); critic 1's forward
      (S_c) and the gradient back to the action: input gradients behind the
      first layer and the action's columns of the first (S'_c + act * width).
    The forward -Q_1(obs, mu(obs)) that the program also runs on skipped
    updates, for its record, is not in it."""
    obs, act, batch = env["obs_dim"], env["act_dim"], hp["batch_size"]
    actor = c.net_dims(obs, act, hp["hidden"], act, False)
    critic = [(obs + act, hp["hidden"][0])] + c.net_dims(obs, act, hp["hidden"], 1, False)[1:]
    s_a, s_c = (sum(i * o for i, o in net) for net in (actor, critic))
    t_a, t_c = (sum(i * o for i, o in net[1:]) for net in (actor, critic))
    every = 2.0 * batch * (s_a + 2 * (3 * s_c + t_c))
    policy = 2.0 * batch * (2 * s_a + t_a + s_c + t_c + act * critic[0][1])
    values = sum(i * o + o for i, o in actor) + 2 * sum(i * o + o for i, o in critic)
    return {
        "flops": every + policy / float(hp["policy_delay"]),
        "row_bytes": 4.0 * batch * (2 * obs + act + 3),
        # params, mu, nu, target: read and written once each, 4 bytes a value
        "state_bytes": 2.0 * 4 * 4 * values,
    }


def make_step(seed, env, hp, operand_dtype=None):
    mm = products(operand_dtype)
    scale = jnp.asarray(env["action_scale"], jnp.float32)
    offset = jnp.asarray(env["action_offset"], jnp.float32)

    def policy(params, obs):
        return jnp.tanh(c.mlp_body(mm, params, obs)) * scale + offset

    def q(params, obs, action):
        return c.mlp_body(mm, params, jnp.concatenate([obs, action], axis=-1))[..., 0]

    def twin(params, obs, action):
        return jax.vmap(lambda p: q(p, obs, action))(params)  # [2, B]

    def step(s, rows):
        b = c.unpack(rows, env["obs_dim"], env["act_dim"])
        next_q = twin(s["target_critic"], b["next_obs"], policy(s["target_actor"], b["next_obs"]))
        y = b["reward"] + b["discount"] * jnp.min(next_q, axis=0)

        def critic_loss(cp):
            td = y[None, :] - twin(cp, b["obs"], b["action"])
            return jnp.mean(b["weight"][None, :] * jnp.square(td)), jnp.mean(td, axis=0)

        (closs, td), cgrad = jax.value_and_grad(critic_loss, has_aux=True)(s["critic"])
        first = jax.tree.map(lambda x: x[0], s["critic"])  # critic 1, before this update

        def actor_loss(ap):
            return -jnp.mean(q(first, b["obs"], policy(ap, b["obs"])))

        aloss, agrad = jax.value_and_grad(actor_loss)(s["actor"])
        critic, critic_opt = c.adam(s["critic"], cgrad, s["critic_opt"], hp["critic_lr"])
        actor, actor_opt = c.adam(s["actor"], agrad, s["actor_opt"], hp["actor_lr"])
        moved = {
            "actor": actor,
            "actor_opt": actor_opt,
            "target_actor": c.polyak(actor, s["target_actor"], hp["tau"]),
            "target_critic": c.polyak(critic, s["target_critic"], hp["tau"]),
        }
        delayed = s["step"] % hp["policy_delay"] == 0
        # a select, not arithmetic: a skipped update hands the old bits on
        new = jax.tree.map(lambda a, b: jnp.where(delayed, a, b), moved, {k: s[k] for k in moved})
        new.update(critic=critic, critic_opt=critic_opt, step=s["step"] + 1)
        return new, {
            "td": td,
            "critic_loss": closs,
            "actor_loss": aloss,
            "critic_grad_norm": c.tree_norm(cgrad),
            "actor_grad_norm": jnp.where(delayed, c.tree_norm(agrad), 0.0),
            "twin_gap": jnp.mean(jnp.abs(next_q[0] - next_q[1])),
        }

    return step
