"""TD3 (Fujimoto, van Hoof and Meger 2018, arXiv 1802.09477, Algorithm 1), one
update in plain float32 `jax.numpy`: two critics regress on the clipped
double-Q target y = R + d * min_i Q'_i(s', a~), a~ the target policy's action
with clipped Gaussian noise added (target-policy smoothing); on every
`policy_delay`-th update (those whose step count before the update is 0, d,
2d, ..., the phase of the authors' code) the actor ascends Q_1(s, mu(s))
through critic 1 as it stood before this update, and the actor's and both
critics' targets take one Polyak step; on every other update the actor, its
Adam moments and step count, and every target are handed on bit for bit.
Adam for all three nets.

A row is [obs | action | R | d | next_obs | w], d = gamma * (1 - done) folded
in by the replay. The critics are stacked on a leading axis of 2, as the
program's state holds them.

The smoothing noise of update t is `clip(sigma * normal(fold_in(PRNGKey(seed
^ 0x7D3AF), t), (B, act)), -c, c)`, t the step count before the update: the
one random stream both sides must share for the numbers to be comparable at
all. It is added in environment action units, sigma not multiplied by the
action box's half-width `scale` (the authors' code multiplies by the largest
action; at HalfCheetah's scale of 1.0 the two readings are the same number),
and the sum is clipped to the box.

`td`, per sample and signed, is what the program reports as its TD errors on
this branch: the mean over the two critics of y - Q_i(s, a). `actor_loss` on
an update that skips the actor is the forward value -mean Q_1(s, mu(s)),
which the program still computes for its record; `actor_grad_norm` reads 0
there. `twin_gap`, per update, is the batch mean of |Q'_1(s', a~) - Q'_2(s',
a~)|: how much the clipped minimum bites (the program's `td3_twin_gap`, which
a chunk reports for its last update).

Departures from the paper, all the program's, none of them a width (the nets
are as wide as the configuration's `hidden` says, and this file fixes none):
- the critic loss is the MEAN of the two critics' weighted squared errors,
  half the sum the paper writes, so each critic's gradient is half the
  paper's at the same learning rate (Adam divides most of that out);
- the action joins the critics at their second layer (as in DDPG's paper),
  where the authors' code concatenates it to the observation;
- the actors explore with Ornstein-Uhlenbeck noise where the paper has
  Gaussian noise of sigma 0.1, and warm the ring with 1,000 rows where the
  paper takes 10,000 uniformly random steps first (both outside this update).
PAPERS.md holds what this tree knows of the paper's settings.
"""

import jax
import jax.numpy as jnp

from . import common as c
from .d4pg import products  # the rounding as lax.reduce_precision: finite at 400-300 on the TPU


def init(seed, env, hp):
    k_actor, k_critic = jax.random.split(jax.random.PRNGKey(seed))
    k1, k2 = jax.random.split(k_critic)
    actor = c.actor_init(k_actor, env["obs_dim"], env["act_dim"], hp["hidden"])
    critic = jax.tree.map(
        lambda a, b: jnp.stack([a, b]),
        c.critic_init(k1, env["obs_dim"], env["act_dim"], hp["hidden"]),
        c.critic_init(k2, env["obs_dim"], env["act_dim"], hp["hidden"]),
    )
    return {
        "actor": actor,
        "critic": critic,
        "target_actor": actor,
        "target_critic": critic,
        "actor_opt": c.adam_init(actor),
        "critic_opt": c.adam_init(critic),
        "step": jnp.zeros((), jnp.int32),
        # carried in the state so that one compiled reference serves every seed
        "noise_key": jax.random.PRNGKey(seed ^ 0x7D3AF),
    }


def work(env, hp):
    """Operations and bytes of one update (common.work), the algorithm's and
    averaged over the delay's period d: the forward -Q_1(s, mu(s)) that the
    program also runs on skipped updates, for its record, is not in it.
    Actor: target forward on s' (1) on every update + forward and backward
    on s (3) on every d-th = 1 + 3/d. Critics: each a target forward (1) and
    a TD forward and backward (3) on every update, and critic 1 alone a
    forward and backward-to-the-action under the actor (3) on every d-th:
    8 + 3/d over the pair, so 4 + 1.5/d for each."""
    d = float(hp["policy_delay"])
    return c.work(
        env, hp, actor_out=env["act_dim"], n_critics=2,
        actor_passes=1.0 + 3.0 / d, critic_passes=4.0 + 1.5 / d,
    )


def smoothing_noise(key, t, hp, shape):
    """Update t's target-policy smoothing noise, scaled and clipped."""
    eps = hp["target_noise"] * jax.random.normal(jax.random.fold_in(key, t), shape)
    return jnp.clip(eps, -hp["target_noise_clip"], hp["target_noise_clip"])


def make_step(seed, env, hp, operand_dtype=None):
    mm = products(operand_dtype)
    scale = jnp.asarray(env["action_scale"], jnp.float32)
    offset = jnp.asarray(env["action_offset"], jnp.float32)

    def policy(params, obs):
        return jnp.tanh(c.mlp_body(mm, params, obs)) * scale + offset

    def twin(params, obs, action):
        return jax.vmap(lambda p: c.critic_apply(mm, p, obs, action))(params)  # [2, B]

    def step(s, rows):
        b = c.unpack(rows, env["obs_dim"], env["act_dim"])
        eps = smoothing_noise(s["noise_key"], s["step"], hp, b["action"].shape)
        next_a = jnp.clip(policy(s["target_actor"], b["next_obs"]) + eps, offset - scale, offset + scale)
        next_q = twin(s["target_critic"], b["next_obs"], next_a)
        y = b["reward"] + b["discount"] * jnp.min(next_q, axis=0)

        def critic_loss(cp):
            td = y[None, :] - twin(cp, b["obs"], b["action"])
            return jnp.mean(b["weight"][None, :] * jnp.square(td)), jnp.mean(td, axis=0)

        (closs, td), cgrad = jax.value_and_grad(critic_loss, has_aux=True)(s["critic"])
        first = jax.tree.map(lambda x: x[0], s["critic"])  # critic 1, before this update

        def actor_loss(ap):
            return -jnp.mean(c.critic_apply(mm, first, b["obs"], policy(ap, b["obs"])))

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
        new.update(critic=critic, critic_opt=critic_opt, step=s["step"] + 1, noise_key=s["noise_key"])
        return new, {
            "td": td,
            "critic_loss": closs,
            "actor_loss": aloss,
            "critic_grad_norm": c.tree_norm(cgrad),
            "actor_grad_norm": jnp.where(delayed, c.tree_norm(agrad), 0.0),
            "twin_gap": jnp.mean(jnp.abs(next_q[0] - next_q[1])),
        }

    return step
