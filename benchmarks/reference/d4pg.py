"""D4PG (Barth-Maron et al. 2018, arXiv 1804.08617, Algorithm 1 with the
categorical critic of its section 3 and N-step returns, uniform replay), one
update in plain float32 `jax.numpy`: the critic's last layer gives `num_atoms`
logits over the support z_i = v_min + i * dz; the target distribution
p' = softmax(Z'(s', mu'(s'))) is moved to Tz_j = clip(R + d * z_j) and
projected back onto the support; the critic descends the cross-entropy
between that projection and softmax(Z(s, a)); the actor ascends the
critic's expectation sum_i z_i softmax(Z(s, mu(s)))_i through the critic as
it stood before this update; Adam for both, Polyak targets.

A row is [obs | action | R | d | next_obs | w]: the actors have already made
R = sum_{k<n} gamma^k r_{t+k} and d = gamma^n * (1 - done), so n appears
nowhere here.

The projection is written in its dense form, m_i = sum_j p'_j *
max(0, 1 - |b_j - i|) with b_j = (Tz_j - v_min) / dz, as one [B, A, A]
product: no floor/ceil and no scatter, so it shares no implementation with
the program's `categorical_projection` (floor/ceil and one-hots) or with the
kernel's loop over atoms.

`td`, per sample and signed, is what the program reports as its TD errors on
this branch (`ops/losses.py:distributional_critic_loss`): the projection's
expectation minus the critic's, sum_i z_i m_i - sum_i z_i softmax(Z(s, a))_i.
The projection keeps expectations, so `td` barely sees a wrong projection:
the number that holds the projection is the mean cross-entropy
(`critic_loss`).

`edge_mass`, per update, is the batch mean of m_0 + m_{A-1}: the share of
the projected target that the support's two ends hold (the program's
`c51_edge_mass`, which a chunk reports for its last update).

Departures from the paper, all the program's, none of them a width: the nets
are as wide as the configuration's `hidden` says, and this file fixes none.
- Polyak targets every update (tau) where the paper copies the target
  networks every 100 steps;
- the actors explore with Ornstein-Uhlenbeck noise where the paper has
  Gaussian noise of sigma 0.3 (outside this update);
- the action joins the critic at its second layer (as in DDPG's paper);
- v_min / v_max as the configuration gives them (the paper sets them by task);
- uniform replay: the weight w is 1 on every row.
PAPERS.md holds what this tree knows of the paper's appendix.
"""

import jax
import jax.numpy as jnp

from . import common as c


def products(operand_dtype=None):
    """`common.products` with the rounding written as `lax.reduce_precision`
    to the dtype's exponent and mantissa bits, not as a cast to the dtype and
    back: the same values wherever the dtype holds them as normal numbers
    (what it holds as subnormals, under 6e-5 for float8_e5m2, goes to zero),
    and no array of a float8 type in the program. At 400-300 the TPU's
    compiler (libtpu 0.0.34) turns every output of the update into NaN when
    its operands pass through float8_e5m2 arrays: 256-256, 384-256, 400-256
    and 256-300 are finite, each product of 400-300 taken alone is finite,
    the whole step is not (my chip run, PR 27). `common.py` is not this
    PR's to edit."""
    if operand_dtype is None:
        return c.products(None)
    info = jnp.finfo(jnp.dtype(operand_dtype))

    def dot(a, b):
        a, b = (jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant) for x in (a, b))
        return jnp.dot(a, b, precision=c.HIGHEST)

    @jax.custom_vjp
    def mm(x, w):  # x [B, in], w [in, out]
        return dot(x, w)

    mm.defvjp(lambda x, w: (dot(x, w), (x, w)), lambda xw, g: (dot(g, xw[1].T), dot(xw[0].T, g)))
    return mm


def critic_init(key, obs_dim, act_dim, hidden, num_atoms):
    """`common.critic_init` with a last layer `num_atoms` wide."""
    dims = [obs_dim, *hidden, num_atoms]
    keys = jax.random.split(key, len(dims) - 1)
    return tuple(
        c.linear_init(keys[i], dims[i] + (act_dim if i == 1 else 0), dims[i + 1], i == len(dims) - 2)
        for i in range(len(dims) - 1)
    )


def init(seed, env, hp):
    k_actor, k_critic = jax.random.split(jax.random.PRNGKey(seed))
    actor = c.actor_init(k_actor, env["obs_dim"], env["act_dim"], hp["hidden"])
    critic = critic_init(k_critic, env["obs_dim"], env["act_dim"], hp["hidden"], hp["num_atoms"])
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
    """Operations and bytes of one update, counted as `common.work` counts
    them (matmul operations only; parameters, moments and targets read and
    written once a launch; each update's rows read once), with the critic's
    head `num_atoms` wide. Actor: target forward on s' (1) + forward and
    backward on s (3) = 4. Critic: target forward (1) + cross-entropy
    forward and backward (3) + forward and backward-to-the-action under the
    actor (3) = 7. Softmax, projection and Adam are elementwise and not
    counted."""
    obs, act, batch = env["obs_dim"], env["act_dim"], hp["batch_size"]
    actor = c.net_dims(obs, act, hp["hidden"], act, False)
    critic = c.net_dims(obs, act, hp["hidden"], hp["num_atoms"], True)
    f_actor = 2.0 * batch * sum(i * o for i, o in actor)
    f_critic = 2.0 * batch * sum(i * o for i, o in critic)
    values = sum(i * o + o for i, o in actor) + sum(i * o + o for i, o in critic)
    return {
        "flops": 4.0 * f_actor + 7.0 * f_critic,
        "row_bytes": 4.0 * batch * (2 * obs + act + 3),
        "state_bytes": 2.0 * 4 * 4 * values,
    }


def support(hp):
    """(z f32[A], dz)."""
    n = hp["num_atoms"]
    dz = (hp["v_max"] - hp["v_min"]) / (n - 1)
    return hp["v_min"] + dz * jnp.arange(n, dtype=jnp.float32), dz


def project(hp, probs, ret, disc):
    """The target distribution `probs` [B, A] under z -> clip(ret + disc * z),
    put back on the support: m_i = sum_j p'_j * max(0, 1 - |b_j - i|)."""
    z, dz = support(hp)
    tz = jnp.clip(ret[:, None] + disc[:, None] * z[None, :], hp["v_min"], hp["v_max"])
    b = (tz - hp["v_min"]) / dz  # [B, A(j)]
    i = jnp.arange(hp["num_atoms"], dtype=jnp.float32)
    share = jnp.maximum(0.0, 1.0 - jnp.abs(b[:, :, None] - i[None, None, :]))  # [B, j, i]
    return jnp.sum(probs[:, :, None] * share, axis=1)


def make_step(seed, env, hp, operand_dtype=None):
    mm = products(operand_dtype)
    scale = jnp.asarray(env["action_scale"], jnp.float32)
    offset = jnp.asarray(env["action_offset"], jnp.float32)
    z, _ = support(hp)

    def policy(params, obs):
        return jnp.tanh(c.mlp_body(mm, params, obs)) * scale + offset

    def logits(params, obs, action):
        x = jax.nn.relu(mm(obs, params[0]["w"]) + params[0]["b"])
        return c.mlp_body(mm, params[1:], jnp.concatenate([x, action], axis=-1))

    def step(s, rows):
        b = c.unpack(rows, env["obs_dim"], env["act_dim"])
        next_p = jax.nn.softmax(
            logits(s["target_critic"], b["next_obs"], policy(s["target_actor"], b["next_obs"])), axis=-1
        )
        m = project(hp, next_p, b["reward"], b["discount"])

        def critic_loss(cp):
            lg = logits(cp, b["obs"], b["action"])
            ce = -jnp.sum(m * jax.nn.log_softmax(lg, axis=-1), axis=-1)
            td = jnp.sum(m * z, axis=-1) - jnp.sum(jax.nn.softmax(lg, axis=-1) * z, axis=-1)
            return jnp.mean(b["weight"] * ce), td

        (closs, td), cgrad = jax.value_and_grad(critic_loss, has_aux=True)(s["critic"])

        def actor_loss(ap):
            lg = logits(s["critic"], b["obs"], policy(ap, b["obs"]))
            return -jnp.mean(jnp.sum(jax.nn.softmax(lg, axis=-1) * z, axis=-1))

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
            "edge_mass": jnp.mean(m[:, 0] + m[:, -1]),
        }

    return step
