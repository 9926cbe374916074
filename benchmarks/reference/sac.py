"""Soft actor-critic (Haarnoja et al. 2018, arXiv 1812.05905, Algorithm 1,
eqs. 5-7, 17-18, App. C), one update in plain float32 `jax.numpy`: twin
critics on y = r + discount * (min_i Q'_i(s', a') - alpha * log pi(a'|s')),
a' from the current policy; reparameterised tanh-Gaussian actor on
E[alpha * log pi - min_i Q_i]; learned temperature towards -dim(A).

Departures from the paper, all the program's and stated so that the two can
be compared (none changes a width):
- log_std is squashed onto [-5, 2] by a tanh, where the authors' code clips
  to [-20, 2];
- the density is taken in environment action units (the box's half-width
  `scale` sits inside the log-det term, with 1e-6 added), so the target
  entropy is -dim(A) + sum(log scale);
- the critic loss is the mean over both critics' squared errors, the action
  joins the critic at its second layer (as in DDPG's paper), the
  temperature's Adam uses the critics' learning rate, and the actor is
  differentiated against the critics as they stood before this update;
- the policy noise of update t is `normal(split(fold_in(PRNGKey(seed ^
  0x5AC0), t)))`, next-state draw first: the one random stream both sides
  must share for the numbers to be comparable at all.
"""

import jax
import jax.numpy as jnp

from . import common as c

LOG_STD_MIN, LOG_STD_MAX = -5.0, 2.0


def init(seed, env, hp):
    k_actor, k_critic = jax.random.split(jax.random.PRNGKey(seed))
    k1, k2 = jax.random.split(k_critic)
    actor = c.actor_init(k_actor, env["obs_dim"], 2 * env["act_dim"], hp["hidden"])
    critic = jax.tree.map(
        lambda a, b: jnp.stack([a, b]),
        c.critic_init(k1, env["obs_dim"], env["act_dim"], hp["hidden"]),
        c.critic_init(k2, env["obs_dim"], env["act_dim"], hp["hidden"]),
    )
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
    """Operations and bytes of one update (common.work). Actor (head 2 * act
    wide): forward on s' for the target (1) + forward and backward on s (3)
    = 4. Each of the two critics: target forward (1) + TD forward and
    backward (3) + forward and backward-to-the-action under the actor (3)
    = 7."""
    return c.work(env, hp, actor_out=2 * env["act_dim"], n_critics=2, actor_passes=4.0, critic_passes=7.0)


def make_step(seed, env, hp, operand_dtype=None):
    mm = c.products(operand_dtype)
    act_dim = env["act_dim"]
    scale = jnp.broadcast_to(jnp.asarray(env["action_scale"], jnp.float32), (act_dim,))
    offset = jnp.asarray(env["action_offset"], jnp.float32)
    target_entropy = -float(act_dim) + float(jnp.sum(jnp.log(scale)))

    def sample(params, obs, key):
        mean, raw = jnp.split(c.mlp_body(mm, params, obs), 2, axis=-1)
        log_std = LOG_STD_MIN + 0.5 * (LOG_STD_MAX - LOG_STD_MIN) * (jnp.tanh(raw) + 1.0)
        std = jnp.exp(log_std)
        u = mean + std * jax.random.normal(key, mean.shape)
        t = jnp.tanh(u)
        gauss = -0.5 * (jnp.square((u - mean) / std) + 2.0 * log_std + jnp.log(2.0 * jnp.pi))
        log_det = jnp.log(scale * (1.0 - jnp.square(t)) + 1e-6)
        return t * scale + offset, jnp.sum(gauss - log_det, axis=-1)

    def twin(params, obs, action):
        return jax.vmap(lambda p: c.critic_apply(mm, p, obs, action))(params)  # [2, B]

    def step(s, rows):
        b = c.unpack(rows, env["obs_dim"], act_dim)
        k_next, k_cur = jax.random.split(jax.random.fold_in(s["noise_key"], s["step"]))
        alpha = jnp.exp(s["log_alpha"])
        next_a, next_lp = sample(s["actor"], b["next_obs"], k_next)
        next_q = jnp.min(twin(s["target_critic"], b["next_obs"], next_a), axis=0)
        y = b["reward"] + b["discount"] * (next_q - alpha * next_lp)

        def critic_loss(cp):
            td = y[None, :] - twin(cp, b["obs"], b["action"])
            return jnp.mean(b["weight"][None, :] * jnp.square(td)), jnp.mean(td, axis=0)

        (closs, td), cgrad = jax.value_and_grad(critic_loss, has_aux=True)(s["critic"])

        def actor_loss(ap):
            a, lp = sample(ap, b["obs"], k_cur)
            q = jnp.min(twin(s["critic"], b["obs"], a), axis=0)
            return jnp.mean(alpha * lp - q), jnp.mean(lp)

        (aloss, mean_lp), agrad = jax.value_and_grad(actor_loss, has_aux=True)(s["actor"])
        critic, critic_opt = c.adam(s["critic"], cgrad, s["critic_opt"], hp["critic_lr"])
        actor, actor_opt = c.adam(s["actor"], agrad, s["actor_opt"], hp["actor_lr"])
        # J(alpha) = E[-alpha * (log pi + target entropy)], in log(alpha).
        log_alpha, alpha_opt = c.adam(
            s["log_alpha"], -(mean_lp + target_entropy), s["alpha_opt"], hp["critic_lr"]
        )
        new = {
            "actor": actor,
            "critic": critic,
            "target_critic": c.polyak(critic, s["target_critic"], hp["tau"]),
            "actor_opt": actor_opt,
            "critic_opt": critic_opt,
            "log_alpha": log_alpha,
            "alpha_opt": alpha_opt,
            "step": s["step"] + 1,
            "noise_key": s["noise_key"],
        }
        return new, {
            "td": td,
            "critic_loss": closs,
            "actor_loss": aloss,
            "critic_grad_norm": c.tree_norm(cgrad),
            "actor_grad_norm": c.tree_norm(agrad),
        }

    return step
