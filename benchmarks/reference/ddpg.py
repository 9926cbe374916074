"""DDPG (Lillicrap et al. 2015, arXiv 1509.02971, Algorithm 1 and §7), one
update in plain float32 `jax.numpy`: critic TD step on
y = r + discount * Q'(s', mu'(s')), deterministic policy gradient through the
critic as it stood before this update, Adam for both, Polyak targets.
`discount` is gamma * (1 - done), folded into the row by the replay.
No departure from the paper other than the widths the configuration states.
"""

import jax
import jax.numpy as jnp

from . import common as c


def init(seed, env, hp):
    k_actor, k_critic = jax.random.split(jax.random.PRNGKey(seed))
    actor = c.actor_init(k_actor, env["obs_dim"], env["act_dim"], hp["hidden"])
    critic = c.critic_init(k_critic, env["obs_dim"], env["act_dim"], hp["hidden"])
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
    """Operations and bytes of one update (common.work). Actor: target
    forward on s' (1) + forward and backward on s (3) = 4. Critic: target
    forward (1) + TD forward and backward (3) + forward and
    backward-to-the-action under the actor (3) = 7."""
    return c.work(env, hp, actor_out=env["act_dim"], n_critics=1, actor_passes=4.0, critic_passes=7.0)


def make_step(seed, env, hp, operand_dtype=None):
    mm = c.products(operand_dtype)
    scale = jnp.asarray(env["action_scale"], jnp.float32)
    offset = jnp.asarray(env["action_offset"], jnp.float32)

    def policy(params, obs):
        return jnp.tanh(c.mlp_body(mm, params, obs)) * scale + offset

    def step(s, rows):
        b = c.unpack(rows, env["obs_dim"], env["act_dim"])
        next_q = c.critic_apply(
            mm, s["target_critic"], b["next_obs"], policy(s["target_actor"], b["next_obs"])
        )
        y = b["reward"] + b["discount"] * next_q

        def critic_loss(cp):
            td = y - c.critic_apply(mm, cp, b["obs"], b["action"])
            return jnp.mean(b["weight"] * jnp.square(td)), td

        (closs, td), cgrad = jax.value_and_grad(critic_loss, has_aux=True)(s["critic"])

        def actor_loss(ap):
            return -jnp.mean(c.critic_apply(mm, s["critic"], b["obs"], policy(ap, b["obs"])))

        aloss, agrad = jax.value_and_grad(actor_loss)(s["actor"])
        critic, critic_opt = c.adam(s["critic"], cgrad, s["critic_opt"], hp["critic_lr"])
        actor, actor_opt = c.adam(s["actor"], agrad, s["actor_opt"], hp["actor_lr"])
        new = {
            "actor": actor,
            "critic": critic,
            "target_actor": c.polyak(actor, s["target_actor"], hp["tau"]),
            "target_critic": c.polyak(critic, s["target_critic"], hp["tau"]),
            "actor_opt": actor_opt,
            "critic_opt": critic_opt,
            "step": s["step"] + 1,
        }
        return new, {
            "td": td,
            "critic_loss": closs,
            "actor_loss": aloss,
            "critic_grad_norm": c.tree_norm(cgrad),
            "actor_grad_norm": c.tree_norm(agrad),
        }

    return step
