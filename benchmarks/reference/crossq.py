"""CrossQ (Bhatt, Palenicek, Belousov, Argus, Amiranashvili, Brox and Peters,
"CrossQ: Batch Normalization in Deep Reinforcement Learning for Greater
Sample Efficiency and Simplicity", ICLR 2024, arXiv 1902.05605), one update
in plain float32 `jax.numpy`: soft actor-critic without target networks. With
theta the actor, phi_1, phi_2 the critics, and BN_l(x) = g_l * (x - mu) /
sqrt(var + eps) + c_l a batch-norm layer with learned scale g_l and shift c_l
in front of EVERY dense layer of every net (h_0 = BN_0(input), h_l =
BN_l(relu(W_l h_{l-1} + b_l)), output W_last h + b_last):

1. a' is the tanh-Gaussian sample of pi_theta(.|s') from standard normals,
   the actor in EVALUATION mode (mu, var its running statistics), no gradient.
2. X = [(s, a); (s', a')], 2B rows. Each critic runs ONCE on X in TRAINING
   mode (mu and the biased var over all 2B rows, so one set of batch
   statistics normalises both halves): [q_i ; q'_i] = split(Q_phi_i(X)).
3. y = R + d * stop_gradient(min_i q'_i - alpha * log pi_theta(a'|s')); every
   critic takes an Adam step (beta_1 = hp["adam_b1"], the paper's 0.5) on its
   weighted squared error against y, and each BN_l's running mean and
   variance move by 1 - hp["bn_momentum"] towards the 2B-row batch's. No
   target network exists and no Polyak pass runs: `init` returns no
   `target_*` entry.
4. On every `policy_delay`-th update (those whose step count before the
   update is 0, 3, 6, ...): a~ is the sample of pi_theta(.|s), the actor in
   training mode (its own moments over the B rows, its running statistics
   moving), the critics in EVALUATION mode as they stood before this update;
   the actor steps on mean_b(alpha * log pi(a~|s) - min_i Q_phi_i(s, a~)) and
   log alpha on -log alpha * (mean log pi + target entropy). On every other
   update the actor, its statistics, the temperature, their Adam moments and
   step counts are handed on bit for bit.

A row is [obs | action | R | d | next_obs | w], d = gamma * (1 - done) folded
in by the replay. The critics are stacked on a leading axis of 2, each seeded
on its own (`split(k_critic, 2)`); a layer is a dict {w, b, bn_scale,
bn_shift, bn_mean, bn_var}, the names the program's state has, so that the
harness lines the two trees up leaf by leaf: the running statistics are
compared with everything else. The randomness of update t, t the step count
before the update, is SAC's stream: normal(split(fold_in(PRNGKey(seed ^
0x5AC0), t))), next-state draw first.

`td`, per sample and signed, is the mean over the two critics of y - q_i.
`actor_loss` and `actor_grad_norm` read 0 on an update that skips the policy.
`bn_stat_gap`, per update, is the mean over the critics' normalised features
of |mu_batch - mu_running| / sqrt(var_running + eps) (the program's record
key of that name, which a chunk reports for its last update).

Departures from the source, all the program's, none of them a width (the
nets are as wide as `actor_hidden` and `critic_hidden` say, and this file
fixes none):
- SAC's, as reference/sac.py lists them, but one: log_std squashed onto
  [-5, 2] by a tanh; the density in environment action units, so the target
  entropy is -dim(A) + sum(log scale); the critic loss the MEAN over both
  critics' weighted squared errors, half the sum the paper writes (Adam
  divides most of that out); the temperature's Adam at the critics' learning
  rate (and at their beta_1). The one that does not hold here: the action
  joins the critics at their INPUT, where BN_0 normalises it with the
  observation (the configuration's --action_insert_layer=0);
- plain batch normalisation, where the authors' later code (SBX) clips a
  correction towards the running statistics (batch renormalisation): that
  correction is the identity during that code's first 1e5 updates, and so in
  any run this benchmark times;
- eps = 1e-3 and momentum 0.99 as SBX's layer has them; the paper's text
  gives the momentum and not eps;
- the placement of BN (in front of every dense layer, the last one too) is
  SBX's, known fairly and not firmly (PAPERS.md);
- the loop: a decoupled learner free-runs beside its actors; the policy steps
  on the first of each 3 updates.
PAPERS.md holds what this tree knows of the paper's settings.
"""

import jax
import jax.numpy as jnp

from . import common as c
from .d4pg import products  # the rounding as lax.reduce_precision, no float8 array in the program

LOG_STD_MIN, LOG_STD_MAX = -5.0, 2.0
STATS = ("bn_mean", "bn_var")


def with_norm(params):
    """An identity batch-norm layer in front of every dense layer."""
    def bn(n):
        return {"bn_scale": jnp.ones((n,)), "bn_shift": jnp.zeros((n,)),
                "bn_mean": jnp.zeros((n,)), "bn_var": jnp.ones((n,))}

    return tuple({**layer, **bn(layer["w"].shape[0])} for layer in params)


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
    actor = with_norm(c.actor_init(k_actor, env["obs_dim"], 2 * env["act_dim"], hp["actor_hidden"]))
    members = [
        with_norm(critic_init(k, env["obs_dim"], env["act_dim"], hp["critic_hidden"]))
        for k in jax.random.split(k_critic, 2)
    ]
    critic = jax.tree.map(lambda *m: jnp.stack(m), *members)
    log_alpha = jnp.log(jnp.asarray(hp["alpha0"], jnp.float32))
    return {
        "actor": actor,
        "critic": critic,
        "actor_opt": c.adam_init(actor),
        "critic_opt": c.adam_init(critic),
        "log_alpha": log_alpha,
        "alpha_opt": c.adam_init(log_alpha),
        "step": jnp.zeros((), jnp.int32),
        # carried in the state so that one compiled reference serves every seed
        "noise_key": jax.random.PRNGKey(seed ^ 0x5AC0),
    }


def work(env, hp):
    """{"flops", "row_bytes", "state_bytes"} of one update, what the
    algorithm needs and each once, averaged over the policy's period
    (common.py's conventions: matmul operations only, 2 * rows * in * out a
    product; the state read and written once a launch). With S(net) the sum
    of in * out over a net's layers and S'(net) that sum without the first
    layer (no gradient is needed with respect to a net's input rows):
    - every update: the actor's forward on the B rows s' (S_a); each of the
      two critics' joint forward on 2B rows (S_c), its weight gradients on
      2B rows (S_c: y carries no gradient, but the shared batch statistics
      carry one into the second half's rows) and its input gradients behind
      the first layer (S'_c);
    - every `policy_delay`-th: the actor's forward, weight gradients and
      input gradients on B rows (2 S_a + S'_a); each critic's evaluation-mode
      forward on B rows (S_c) and the gradient back to the action: input
      gradients behind the first layer and the action's columns of the first
      (S'_c + act * width).
    No target exists: the state is parameters and both Adam moments."""
    obs, act, batch = env["obs_dim"], env["act_dim"], hp["batch_size"]
    g = float(hp["policy_delay"])
    actor = c.net_dims(obs, act, hp["actor_hidden"], 2 * act, False)
    critic = [(obs + act, hp["critic_hidden"][0])] + c.net_dims(obs, act, hp["critic_hidden"], 1, False)[1:]
    s_a, s_c = (sum(i * o for i, o in net) for net in (actor, critic))
    t_a, t_c = (sum(i * o for i, o in net[1:]) for net in (actor, critic))
    every = 2.0 * batch * s_a + 2 * 2.0 * (2 * batch) * (2 * s_c + t_c)
    policy = 2.0 * batch * (2 * s_a + t_a) + 2 * 2.0 * batch * (s_c + t_c + act * critic[0][1])
    # w, b and the four batch-norm vectors over a layer's inputs
    values = sum(i * o + o + 4 * i for i, o in actor) + 2 * sum(i * o + o + 4 * i for i, o in critic)
    return {
        "flops": every + policy / g,
        "row_bytes": 4.0 * batch * (2 * obs + act + 3),
        # params, mu, nu: read and written once each, 4 bytes a value
        "state_bytes": 2.0 * 4 * 3 * values,
    }


def draws(key, t, hp, shape):
    """Update t's randomness: (normals at s', normals at s)."""
    k_next, k_cur = jax.random.split(jax.random.fold_in(key, t))
    return jax.random.normal(k_next, shape), jax.random.normal(k_cur, shape)


# The algorithm's choices, each a function of its own so that a test can bend
# one and see the comparison fail (tests/test_reference_crossq.py).


def joint_values(critics, obs, action, next_obs, next_action):
    """(q [2, B], q' [2, B], the pass's moments): ONE training-mode pass of
    each critic over the 2B rows [(s, a); (s', a')], split afterwards."""
    x = jnp.concatenate([jnp.concatenate([obs, action], -1), jnp.concatenate([next_obs, next_action], -1)])
    q, moments = critics(x, True)
    return q[:, : obs.shape[0]], q[:, obs.shape[0] :], moments


def bootstrap(next_q, evaluate):
    """What the Bellman target reads at (s', a'): the joint pass's own q'
    [2, B]. `evaluate(critic params)` is an evaluation-mode pass at (s', a')
    for a bent reference that reads some other network instead."""
    return next_q


def policy_steps(step, hp):
    """Whether update `step` (the count before it) moves actor and temperature."""
    return step % hp["policy_delay"] == 0


def adam_b1(hp):
    return hp["adam_b1"]


def make_step(seed, env, hp, operand_dtype=None):
    mm = products(operand_dtype)
    act_dim = env["act_dim"]
    scale = jnp.broadcast_to(jnp.asarray(env["action_scale"], jnp.float32), (act_dim,))
    offset = jnp.asarray(env["action_offset"], jnp.float32)
    target_entropy = -float(act_dim) + float(jnp.sum(jnp.log(scale)))
    momentum, eps, b1 = hp["bn_momentum"], hp["bn_eps"], adam_b1(hp)

    def body(params, x, train):
        """The normalised MLP on rows x: (output, [(mu, var) a layer])."""
        moments = []
        for i, layer in enumerate(params):
            if train:
                mu = jnp.mean(x, axis=0)
                var = jnp.mean(jnp.square(x - mu), axis=0)
            else:
                mu, var = layer["bn_mean"], layer["bn_var"]
            moments.append((mu, var))
            x = layer["bn_scale"] * (x - mu) / jnp.sqrt(var + eps) + layer["bn_shift"]
            x = mm(x, layer["w"]) + layer["b"]
            if i < len(params) - 1:
                x = jax.nn.relu(x)
        return x, moments

    def running(params, moments):
        """`params` with the running statistics a step towards `moments`."""
        return tuple(
            {**layer, "bn_mean": momentum * layer["bn_mean"] + (1 - momentum) * mu,
             "bn_var": momentum * layer["bn_var"] + (1 - momentum) * var}
            for layer, (mu, var) in zip(params, moments)
        )

    def keep_stats(new, old):
        """`new`'s trained leaves with `old`'s statistics: Adam has no say in them."""
        return tuple({**n, **{k: o[k] for k in STATS}} for n, o in zip(new, old))

    def sample(params, obs, eps_, train):
        out, moments = body(params, obs, train)
        mean, raw = jnp.split(out, 2, axis=-1)
        log_std = LOG_STD_MIN + 0.5 * (LOG_STD_MAX - LOG_STD_MIN) * (jnp.tanh(raw) + 1.0)
        std = jnp.exp(log_std)
        u = mean + std * eps_
        t = jnp.tanh(u)
        gauss = -0.5 * (jnp.square((u - mean) / std) + 2.0 * log_std + jnp.log(2.0 * jnp.pi))
        log_det = jnp.log(scale * (1.0 - jnp.square(t)) + 1e-6)
        return t * scale + offset, jnp.sum(gauss - log_det, axis=-1), moments

    def twin(params):
        """The two critics as one function of (rows, training mode?):
        ([2, rows], moments stacked on the critics' axis)."""
        def run(x, train):
            q, moments = jax.vmap(lambda p: body(p, x, train))(params)
            return q[..., 0], moments
        return run

    def adam(params, grads, opt, lr):
        count = opt["count"] + 1
        n = count.astype(jnp.float32)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"], grads)
        nu = jax.tree.map(lambda v, g: c.ADAM_B2 * v + (1 - c.ADAM_B2) * g * g, opt["nu"], grads)
        new = jax.tree.map(
            lambda p, m, v: p - lr * (m / (1 - b1**n)) / (jnp.sqrt(v / (1 - c.ADAM_B2**n)) + c.ADAM_EPS),
            params, mu, nu,
        )
        return new, {"mu": mu, "nu": nu, "count": count}

    def step(s, rows):
        b = c.unpack(rows, env["obs_dim"], act_dim)
        eps_next, eps_cur = draws(s["noise_key"], s["step"], hp, b["action"].shape)
        alpha = jnp.exp(s["log_alpha"])
        next_a, next_lp, _ = sample(s["actor"], b["next_obs"], eps_next, False)

        def critic_loss(cp):
            q, next_q, moments = joint_values(twin(cp), b["obs"], b["action"], b["next_obs"], next_a)
            next_q = bootstrap(
                next_q, lambda p: twin(p)(jnp.concatenate([b["next_obs"], next_a], -1), False)[0]
            )
            y = b["reward"] + b["discount"] * jax.lax.stop_gradient(jnp.min(next_q, axis=0) - alpha * next_lp)
            td = y[None, :] - q
            return jnp.mean(b["weight"][None, :] * jnp.square(td)), (jnp.mean(td, axis=0), moments)

        (closs, (td, c_moments)), cgrad = jax.value_and_grad(critic_loss, has_aux=True)(s["critic"])

        def actor_loss(ap):
            a, lp, moments = sample(ap, b["obs"], eps_cur, True)
            q = jnp.min(twin(s["critic"])(jnp.concatenate([b["obs"], a], -1), False)[0], axis=0)
            return jnp.mean(alpha * lp - q), (jnp.mean(lp), moments)

        (aloss, (mean_lp, a_moments)), agrad = jax.value_and_grad(actor_loss, has_aux=True)(s["actor"])
        critic, critic_opt = adam(s["critic"], cgrad, s["critic_opt"], hp["critic_lr"])
        critic = running(keep_stats(critic, s["critic"]), c_moments)
        actor, actor_opt = adam(s["actor"], agrad, s["actor_opt"], hp["actor_lr"])
        actor = running(keep_stats(actor, s["actor"]), a_moments)
        # J(alpha) = E[-alpha * (log pi + target entropy)], in log(alpha).
        log_alpha, alpha_opt = adam(s["log_alpha"], -(mean_lp + target_entropy), s["alpha_opt"], hp["critic_lr"])
        policy = {"actor": actor, "actor_opt": actor_opt, "log_alpha": log_alpha, "alpha_opt": alpha_opt}
        moves = policy_steps(s["step"], hp)
        # a select, not arithmetic: a skipped update hands the old bits on
        new = jax.tree.map(lambda a, b: jnp.where(moves, a, b), policy, {k: s[k] for k in policy})
        new.update(critic=critic, critic_opt=critic_opt, step=s["step"] + 1, noise_key=s["noise_key"])
        gaps = [
            jnp.abs(mu - layer["bn_mean"]) / jnp.sqrt(layer["bn_var"] + eps)
            for layer, (mu, _) in zip(s["critic"], c_moments)
        ]
        return new, {
            "td": td,
            "critic_loss": closs,
            "actor_loss": jnp.where(moves, aloss, 0.0),
            "critic_grad_norm": c.tree_norm(cgrad),
            "actor_grad_norm": jnp.where(moves, c.tree_norm(agrad), 0.0),
            "bn_stat_gap": sum(jnp.sum(g) for g in gaps) / sum(g.size for g in gaps),
        }

    return step
