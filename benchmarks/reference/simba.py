"""SAC on SimBa nets (Lee, Hwang, Kim et al., "SimBa: Simplicity Bias for
Scaling Up Parameters in Deep Reinforcement Learning", ICLR 2025, arXiv
2410.09754; its SAC settings), one update in plain float32 `jax.numpy`. A
net, for an input row o (a critic's is (s, a), an actor's s):

1. RSNorm: s~ = (s - mu) / sqrt(var + 1e-8), mu and var the running mean and
   (biased) variance of every observation row the normaliser has seen, per
   feature, no gradient. A critic's action columns are not normalised: its
   input is concat(s~, a).
2. embedding: x_0 = W_e input + b_e.
3. N residual blocks, pre-LayerNorm, inverted bottleneck of 4:
   x_{i+1} = x_i + W2 relu(W1 LN(x_i) + b1) + b2, W1 [h, 4h], W2 [4h, h];
   LN(x) = g * (x - mean(x)) / sqrt(var(x) + 1e-6) + c over the row's h
   features, g and c learned.
4. post-LayerNorm z = LN(x_N) and the head: a scalar for a critic, SAC's
   [mean | log_std] for the actor.

The update is reference/sac.py's (twin critics on y = R + d * (min_i Q'_i(s',
a') - alpha * log pi(a'|s')), a' from the current policy; the actor on
mean(alpha * log pi - min_i Q_i) against the critics as they stood; the
temperature in log alpha towards the target entropy), with three changes:
AdamW (decoupled decay hp["weight_decay"] on every trained leaf of actor and
critics, p <- p - lr * (adam's step + decay * p); none on the temperature),
the target entropy -hp["target_entropy_scale"] * dim(A), and the normaliser.

The normaliser, and the one DEPARTURE this file makes from the source on
purpose: the source feeds RSNorm every observation the agent sees, once, as
it acts. The harness hands a reference a seeded state and the rows of each
update and nothing else, so here update k FEEDS THE STATISTICS FROM THE `obs`
ROWS OF ITS OWN BATCH (Chan's merge of the batch's moments into the running
ones, count + B), after it has used them: every net of update k (the
critics, their targets and the actor, at s and at s') normalises with the
statistics as they stood when update k began, mean 0 and variance 1 before
the first. A uniform draw from the ring has the ring's moments; what differs
from the source is the weight of recent rows. The state has ONE normaliser;
each net's tree carries a copy of it (`rs_mean`, `rs_var`, `rs_count` beside
the embedding, the names the program's state has, so the harness lines the
trees up leaf by leaf), and the target critics read the online copy.

A row is [obs | action | R | d | next_obs | w], d = gamma * (1 - done) folded
in by the replay. The critics are stacked on a leading axis of 2, each seeded
on its own (`split(k_critic, 2)`). The randomness of update t is SAC's
stream: normal(split(fold_in(PRNGKey(seed ^ 0x5AC0), t))), next-state draw
first. `td`, per sample and signed, is the mean over the two critics of y -
q_i. `resid_share`, `rsnorm_count` and `rsnorm_drift` per update are the
program's record keys of those names.

Other departures from the source, all the program's, none of them a width
(the nets are as wide as `actor_hidden` and `critic_hidden` say, one block
an entry):
- SAC's, as reference/sac.py lists them, but one: log_std squashed onto
  [-5, 2] by a tanh; the density in environment action units, so the target
  entropy is -scale * dim(A) + sum(log action scale); the critic loss the
  MEAN over both critics' weighted squared errors; the temperature's Adam at
  the critics' learning rate. The one that does not hold here: the action
  joins the critics at their input, as the source has it;
- initialisers: this tree's (hidden layers U(+-1/sqrt(fan_in)), heads
  U(+-3e-3)), where the source's code uses orthogonal ones (known fairly);
- LayerNorm's eps 1e-6 and RSNorm's 1e-8 are the source code's library
  defaults as this tree knows them (fairly); clipped double Q and discount
  0.99 are what the source uses on some of its suites (PAPERS.md);
- the loop: a decoupled learner free-runs beside its actors; the source's
  update-to-data ratio of 2 is not a cap here.
PAPERS.md holds what this tree knows of the paper's settings.
"""

import jax
import jax.numpy as jnp

from . import common as c
from .d4pg import products  # the rounding as lax.reduce_precision, no float8 array in the program

LOG_STD_MIN, LOG_STD_MAX = -5.0, 2.0
RS_EPS, LN_EPS, EXPANSION = 1e-8, 1e-6, 4
STATS = ("rs_mean", "rs_var", "rs_count")


def net_init(key, obs_dim, in_dim, out_dim, hidden):
    """(embedding with the statistics, one dict a block, head with the
    post-LayerNorm): the first `obs_dim` of the `in_dim` inputs are
    normalised."""
    h = hidden[0]
    keys = jax.random.split(key, len(hidden) + 2)
    ln = lambda: {"ln_scale": jnp.ones((h,)), "ln_shift": jnp.zeros((h,))}
    embed = {
        **c.linear_init(keys[0], in_dim, h, False),
        "rs_mean": jnp.zeros((obs_dim,)), "rs_var": jnp.ones((obs_dim,)), "rs_count": jnp.zeros(()),
    }
    blocks = []
    for k in keys[1:-1]:
        k1, k2 = jax.random.split(k)
        up, down = c.linear_init(k1, h, EXPANSION * h, False), c.linear_init(k2, EXPANSION * h, h, False)
        blocks.append({**ln(), "w1": up["w"], "b1": up["b"], "w2": down["w"], "b2": down["b"]})
    return (embed, *blocks, {**ln(), **c.linear_init(keys[-1], h, out_dim, True)})


def init(seed, env, hp):
    obs, act = env["obs_dim"], env["act_dim"]
    k_actor, k_critic = jax.random.split(jax.random.PRNGKey(seed))
    actor = net_init(k_actor, obs, obs, 2 * act, hp["actor_hidden"])
    members = [net_init(k, obs, obs + act, 1, hp["critic_hidden"]) for k in jax.random.split(k_critic, 2)]
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


def net_dims(in_dim, out_dim, hidden):
    """(fan in, fan out) of every product of a net, in order."""
    h = hidden[0]
    return [(in_dim, h)] + [d for _ in hidden for d in ((h, EXPANSION * h), (EXPANSION * h, h))] + [(h, out_dim)]


def net_values(obs_dim, in_dim, out_dim, hidden):
    """Trained values of a net: products with their biases, and a scale and a
    shift for each LayerNorm (one a block, one in front of the head)."""
    return sum(i * o + o for i, o in net_dims(in_dim, out_dim, hidden)) + 2 * hidden[0] * (len(hidden) + 1)


def work(env, hp):
    """{"flops", "row_bytes", "state_bytes"} of one update (common.py's
    conventions: matmul operations only, 2 * rows * in * out a product, a
    backward pass two forwards, each pass once; the state read and written
    once a launch). With S(net) the sum of in * out over a net's products:
    each of the two critics runs its TD pass forward and backward on (s, a)
    (3 S_c), its target forward on (s', a') (S_c) and forward and backward to
    the action under the actor's loss (3 S_c): 7 S_c, as reference/sac.py
    counts; the actor runs forward on s' (S_a) and forward and backward on s
    (3 S_a). LayerNorm, RSNorm and the residual sums are elementwise and not
    counted. State: the trained values of actor and critics with both Adam
    moments, the target critics, and the statistics' copies (three nets
    online, two targets)."""
    obs, act, batch = env["obs_dim"], env["act_dim"], hp["batch_size"]
    s_a = sum(i * o for i, o in net_dims(obs, 2 * act, hp["actor_hidden"]))
    s_c = sum(i * o for i, o in net_dims(obs + act, 1, hp["critic_hidden"]))
    v_a = net_values(obs, obs, 2 * act, hp["actor_hidden"])
    v_c = net_values(obs, obs + act, 1, hp["critic_hidden"])
    stats = 2 * obs + 1
    return {
        "flops": 2.0 * batch * (4.0 * s_a + 2 * 7.0 * s_c),
        "row_bytes": 4.0 * batch * (2 * obs + act + 3),
        # params, mu, nu of actor and critics and the target critics: read and written once each
        "state_bytes": 2.0 * 4 * (3 * (v_a + 2 * v_c + 3 * stats) + 2 * (v_c + stats)),
    }


# The algorithm's choices, each a function of its own so that a test can bend
# one and see the comparison fail (tests/test_reference_simba.py).


def decay(hp):
    return hp["weight_decay"]


def statistics_for_targets(s):
    """The statistics the target critics normalise with: the online copy."""
    return {k: s["critic"][0][k] for k in STATS}


def merged(stats, obs):
    """`stats` = (mean, var, count) after the rows `obs` have joined them."""
    mean0, var0, n0 = stats
    rows = obs.shape[0]
    b_mean = jnp.mean(obs, axis=0)
    b_var = jnp.mean(jnp.square(obs - b_mean), axis=0)
    n = n0 + rows
    delta = b_mean - mean0
    mean = mean0 + delta * (rows / n)
    var = (n0 * var0 + rows * b_var) / n + jnp.square(delta) * (n0 * rows / (n * n))
    return (mean, var, n), jnp.mean(jnp.abs(delta) / jnp.sqrt(var0 + RS_EPS))


def make_step(seed, env, hp, operand_dtype=None):
    mm = products(operand_dtype)
    obs_dim, act_dim = env["obs_dim"], env["act_dim"]
    scale = jnp.broadcast_to(jnp.asarray(env["action_scale"], jnp.float32), (act_dim,))
    offset = jnp.asarray(env["action_offset"], jnp.float32)
    target_entropy = -hp["target_entropy_scale"] * float(act_dim) + float(jnp.sum(jnp.log(scale)))
    wd = decay(hp)

    def layer_norm(x, layer):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return layer["ln_scale"] * (x - mean) / jnp.sqrt(var + LN_EPS) + layer["ln_shift"]

    def body(params, stats, obs, action=None):
        """(output, residual share row by row) of a net whose input
        normaliser holds `stats`."""
        x = (obs - stats["rs_mean"]) / jnp.sqrt(stats["rs_var"] + RS_EPS)
        if action is not None:
            x = jnp.concatenate([x, action], axis=-1)
        x = mm(x, params[0]["w"]) + params[0]["b"]
        shares = []
        for block in params[1:-1]:
            f = jax.nn.relu(mm(layer_norm(x, block), block["w1"]) + block["b1"])
            f = mm(f, block["w2"]) + block["b2"]
            shares.append(jnp.linalg.norm(f, axis=-1) / jnp.linalg.norm(x + f, axis=-1))
            x = x + f
        return mm(layer_norm(x, params[-1]), params[-1]["w"]) + params[-1]["b"], sum(shares) / len(shares)

    def sample(params, obs, eps_):
        out, _ = body(params, own(params), obs)
        mean, raw = jnp.split(out, 2, axis=-1)
        log_std = LOG_STD_MIN + 0.5 * (LOG_STD_MAX - LOG_STD_MIN) * (jnp.tanh(raw) + 1.0)
        std = jnp.exp(log_std)
        u = mean + std * eps_
        t = jnp.tanh(u)
        gauss = -0.5 * (jnp.square((u - mean) / std) + 2.0 * log_std + jnp.log(2.0 * jnp.pi))
        log_det = jnp.log(scale * (1.0 - jnp.square(t)) + 1e-6)
        return t * scale + offset, jnp.sum(gauss - log_det, axis=-1)

    def twin(params, stats, obs, action):
        """([2, rows], share [2, rows]); `stats` leaves carry the critics' axis."""
        q, share = jax.vmap(lambda p, st: body(p, st, obs, action))(params, stats)
        return q[..., 0], share

    def own(params):
        """A net's own copy of the statistics; no gradient reaches them."""
        return jax.lax.stop_gradient({k: params[0][k] for k in STATS})

    def adamw(params, grads, opt, lr):
        count = opt["count"] + 1
        n = count.astype(jnp.float32)
        mu = jax.tree.map(lambda m, g: c.ADAM_B1 * m + (1 - c.ADAM_B1) * g, opt["mu"], grads)
        nu = jax.tree.map(lambda v, g: c.ADAM_B2 * v + (1 - c.ADAM_B2) * g * g, opt["nu"], grads)
        new = jax.tree.map(
            lambda p, m, v: p
            - lr * ((m / (1 - c.ADAM_B1**n)) / (jnp.sqrt(v / (1 - c.ADAM_B2**n)) + c.ADAM_EPS) + wd * p),
            params, mu, nu,
        )
        return new, {"mu": mu, "nu": nu, "count": count}

    def with_stats(params, stats):
        """`params` with the normaliser's statistics `stats` = (mean, var,
        count), spread over a stack's leading axis: the optimiser has no say
        in them."""
        embed = params[0]
        new = {k: jnp.broadcast_to(v, embed[k].shape) for k, v in zip(STATS, stats)}
        return ({**embed, **new}, *params[1:])

    def step(s, rows):
        b = c.unpack(rows, obs_dim, act_dim)
        k_next, k_cur = jax.random.split(jax.random.fold_in(s["noise_key"], s["step"]))
        eps_next = jax.random.normal(k_next, b["action"].shape)
        eps_cur = jax.random.normal(k_cur, b["action"].shape)
        alpha = jnp.exp(s["log_alpha"])
        next_a, next_lp = sample(s["actor"], b["next_obs"], eps_next)
        next_q, _ = twin(s["target_critic"], statistics_for_targets(s), b["next_obs"], next_a)
        y = b["reward"] + b["discount"] * (jnp.min(next_q, axis=0) - alpha * next_lp)

        def critic_loss(cp):
            q, share = twin(cp, own(cp), b["obs"], b["action"])
            td = y[None, :] - q
            return jnp.mean(b["weight"][None, :] * jnp.square(td)), (jnp.mean(td, axis=0), jnp.mean(share))

        (closs, (td, share)), cgrad = jax.value_and_grad(critic_loss, has_aux=True)(s["critic"])

        def actor_loss(ap):
            a, lp = sample(ap, b["obs"], eps_cur)
            q, _ = twin(s["critic"], own(s["critic"]), b["obs"], a)
            return jnp.mean(alpha * lp - jnp.min(q, axis=0)), jnp.mean(lp)

        (aloss, mean_lp), agrad = jax.value_and_grad(actor_loss, has_aux=True)(s["actor"])
        a0 = s["actor"][0]
        stats, drift = merged((a0["rs_mean"], a0["rs_var"], a0["rs_count"]), b["obs"])
        critic, critic_opt = adamw(s["critic"], cgrad, s["critic_opt"], hp["critic_lr"])
        critic = with_stats(critic, stats)
        actor, actor_opt = adamw(s["actor"], agrad, s["actor_opt"], hp["actor_lr"])
        actor = with_stats(actor, stats)
        # J(alpha) = E[-alpha * (log pi + target entropy)], in log(alpha); no decay.
        log_alpha, alpha_opt = c.adam(s["log_alpha"], -(mean_lp + target_entropy), s["alpha_opt"], hp["critic_lr"])
        new = {
            "actor": actor,
            "critic": critic,
            "target_critic": with_stats(c.polyak(critic, s["target_critic"], hp["tau"]), stats),
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
            "resid_share": share,
            "rsnorm_count": stats[2],
            "rsnorm_drift": drift,
        }

    return step
