"""Distributional MPO as Acme ships it (Hoffman et al. 2020, "Acme", arXiv
2006.00979: `acme/agents/tf/dmpo` with `acme/tf/losses/mpo.py` and
`acme/tf/networks`; the policy step is Abdolmaleki et al. 2018, arXiv
1806.06920, in the decoupled form of arXiv 1812.02256; the critic is D4PG's,
arXiv 1804.08617), one update in plain float32 `jax.numpy`, written
samples-first ([N, B, ...]) as the source writes it.

The nets are `LayerNormMLP`s: linear -> LayerNorm (eps 1e-5) -> tanh, then
linear -> ELU for each further width, the last one activated too. The policy
pi(o) ends in a diagonal Gaussian head, mean = linear and scale = 0.7 *
softplus(linear) / softplus(0) + 1e-6 (the two linears side by side as one
layer [mean | raw]), no squashing: it lives on the CANONICAL box [-1, 1]^A.
The critic Q(o, a) reads [o | clip(a, -1, 1)] and gives `num_atoms` logits
over the support z_k = v_min + k * dz. pi', Q' are the targets, eta = sp(log
eta), alpha = sp(log alpha) with sp(x) = softplus(x) + 1e-8, every log
variable floored at -18 first.

On rows (o, a, R, d, o'), R the actors' n-step return and d = gamma^n * (1 -
done):

1. E-step, no gradient. (mu', s') = pi'(o'); a_j = mu' + s' * eps_j for j =
   1..N; l_j = Q'(o', clip(a_j)) on all B * N rows; q_j = sum_k z_k
   softmax(l_j)_k.
2. Critic. The target distribution is the MIXTURE of the N: p' =
   softmax(logsumexp_j log_softmax(l_j)); R + d * z is projected back onto
   the support (reference/d4pg.py's dense projection); the loss is the mean
   cross-entropy against Q(o, a_canonical), a_canonical = (a - offset) /
   scale of the ring's environment-unit action.
3. Weights and temperature. w_j = softmax_j(q_j / eta), no gradient; L_eta =
   eta * (epsilon + mean_B logsumexp_j(q_j / eta) - log N). The action
   penalty is a second such pair on the cost -|a_j - clip(a_j)|_2 with its
   own temperature and epsilon_penalty; the two weight sets are ADDED.
4. M-step, decoupled. (mu, s) = pi(o'). L_mu = -mean_B sum_j w_j log N(a_j;
   mu, s'), L_s = -mean_B sum_j w_j log N(a_j; mu', s); KL_mu = mean_B
   KL(N(mu', s') || N(mu, s')) and KL_s = mean_B KL(N(mu', s') || N(mu',
   s)), each a vector over the action's dimensions. Policy loss: L_mu + L_s
   + sum_dim sg(alpha_mu) KL_mu + sum_dim sg(alpha_s) KL_s. Dual loss: L_eta
   + L_penalty + sum_dim alpha_mu (eps_mean - sg(KL_mu)) + sum_dim alpha_s
   (eps_stddev - sg(KL_s)).
5. Adam on policy, critic (their rates) and the duals (`dual_lr`); after
   every update whose count ends a period of `target_update_period`, pi' <-
   pi and Q' <- Q, whole.

`td`, per sample and signed, is what the program reports as its TD errors:
the projection's expectation minus the critic's, as reference/d4pg.py's. The
state carries the duals under `log_alpha` (a dict of the four, the program's
`TrainState.log_alpha`) and their Adam under `alpha_opt`. The draws of update
t are `normal(fold_in(PRNGKey(seed ^ 0x3B0), t), (B, N, A))`: the one random
stream both sides must share for the numbers to be comparable at all.

Departures from the source, all the program's, none of them a width (the
nets are as wide as `actor_hidden` and `critic_hidden` say):
- initialisers: this tree's (hidden layers U(+-1/sqrt(fan_in)), output
  layers U(+-3e-3)) where the source has variance scaling (0.333, uniform,
  fan-out) and 1e-4 on the head;
- the source copies its targets when its step count is a multiple of the
  period BEFORE the update (so also, to no effect, before the first); here
  after the update that ends a period: the same nets at every update;
- the critic reads the ring's action mapped onto the canonical box; the
  actors clip their draw to the box before it reaches the ring (outside this
  update), where the source's environment wrapper clips what it is handed;
- the support as the configuration gives it, uniform replay (w = 1).
PAPERS.md holds what this tree knows of the source's settings.
"""

import math

import jax
import jax.numpy as jnp

from . import common as c
from .d4pg import products, project, support  # lax.reduce_precision rounding; the dense projection

LN_EPS = 1e-5
INIT_SCALE, MIN_SCALE = 0.7, 1e-6
FLOAT_EPS, MIN_LOG = 1e-8, -18.0


def lnmlp_init(key, in_dim, out_dim, hidden):
    first, *rest = c.actor_init(key, in_dim, out_dim, hidden)
    ln = {"ln_scale": jnp.ones((hidden[0],), jnp.float32), "ln_shift": jnp.zeros((hidden[0],), jnp.float32)}
    return (first, ln, *rest)


def init(seed, env, hp):
    k_actor, k_critic = jax.random.split(jax.random.PRNGKey(seed))
    obs, act = env["obs_dim"], env["act_dim"]
    actor = lnmlp_init(k_actor, obs, 2 * act, hp["actor_hidden"])
    critic = lnmlp_init(k_critic, obs + act, hp["num_atoms"], hp["critic_hidden"])
    duals = {
        "log_temperature": jnp.full((1,), hp["init_log_temperature"], jnp.float32),
        "log_penalty_temperature": jnp.full((1,), hp["init_log_temperature"], jnp.float32),
        "log_alpha_mean": jnp.full((act,), hp["init_log_alpha_mean"], jnp.float32),
        "log_alpha_stddev": jnp.full((act,), hp["init_log_alpha_stddev"], jnp.float32),
    }
    return {
        "actor": actor,
        "critic": critic,
        "target_actor": actor,
        "target_critic": critic,
        "actor_opt": c.adam_init(actor),
        "critic_opt": c.adam_init(critic),
        "log_alpha": duals,
        "alpha_opt": c.adam_init(duals),
        "step": jnp.zeros((), jnp.int32),
        # carried in the state so that one compiled reference serves every seed
        "noise_key": jax.random.PRNGKey(seed ^ 0x3B0),
    }


# The four places where this update parts from its neighbours (D4PG's target,
# coupled MPO, MPO without the penalty, Polyak targets), one small function
# each: tests/test_reference_dmpo.py bends them one at a time.


def mixture_of(logp):
    """The critic's target distribution out of the N samples' log
    probabilities [N, B, atoms]: their MIXTURE, not the distribution of
    their mean logits."""
    return jax.nn.softmax(jax.nn.logsumexp(logp, axis=0), axis=-1)


def fixed_pairs(mean, std, mean_t, std_t):
    """The decoupling: the (mean, scale) the mean's fit and bound read, the
    online mean under the TARGET scale, and the pair the scale's read, the
    TARGET mean under the online scale."""
    return (mean, std_t), (mean_t, std)


def penalised(w_value, w_penalty):
    """The M-step's weights with action penalisation on: the two sets added."""
    return w_value + w_penalty


def moved_targets(online, target, step, hp):
    """Both targets copied whole after the update that ends a period."""
    copy = (step + 1) % hp["target_update_period"] == 0
    return jax.tree.map(lambda o, t: jnp.where(copy, o, t), online, target)


def work(env, hp):
    """Operations and bytes of one update, counted as `common.work` counts
    them (matmul operations only, each pass once; parameters, moments and
    targets read and written once a launch; each update's rows read once).
    E-step: the target policy forward on B rows and the target critic
    forward on B * N rows. Critic: forward and backward on B rows (3).
    Policy: forward and backward on B rows (3). `estep_flops` is the first
    alone. Softmaxes, the projection, LayerNorm, the dual step and Adam are
    elementwise and not counted."""
    obs, act, batch, n = env["obs_dim"], env["act_dim"], hp["batch_size"], hp["samples"]
    policy = c.net_dims(obs, act, hp["actor_hidden"], 2 * act, False)
    critic = c.net_dims(obs + act, act, hp["critic_hidden"], hp["num_atoms"], False)
    w_policy = sum(i * o for i, o in policy)
    w_critic = sum(i * o for i, o in critic)
    estep = 2.0 * batch * w_policy + 2.0 * batch * n * w_critic
    values = sum(i * o + o for i, o in policy + critic) + 2 * (hp["actor_hidden"][0] + hp["critic_hidden"][0])
    duals = 2 + 2 * act
    return {
        "flops": estep + 3 * 2.0 * batch * (w_policy + w_critic),
        "estep_flops": estep,
        "row_bytes": 4.0 * batch * (2 * obs + act + 3),
        # nets: params, mu, nu, target; duals: value, mu, nu; read and written once, 4 bytes a value
        "state_bytes": 2.0 * 4 * (4 * values + 3 * duals),
    }


def make_step(seed, env, hp, operand_dtype=None):
    mm = products(operand_dtype)
    obs_dim, act_dim, n = env["obs_dim"], env["act_dim"], hp["samples"]
    scale = jnp.asarray(env["action_scale"], jnp.float32)
    offset = jnp.asarray(env["action_offset"], jnp.float32)
    z, _ = support(hp)

    def body(params, x):
        """LayerNormMLP: LayerNorm's moments and division in float32."""
        x = mm(x, params[0]["w"]) + params[0]["b"]
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        x = jnp.tanh((x - mean) / jnp.sqrt(var + LN_EPS) * params[1]["ln_scale"] + params[1]["ln_shift"])
        for layer in params[2:-1]:
            x = jax.nn.elu(mm(x, layer["w"]) + layer["b"])
        return mm(x, params[-1]["w"]) + params[-1]["b"]

    def policy(params, obs):
        mean, raw = jnp.split(body(params, obs), 2, axis=-1)
        return mean, INIT_SCALE * jax.nn.softplus(raw) / math.log(2.0) + MIN_SCALE

    def logits(params, obs, action):
        return body(params, jnp.concatenate([obs, jnp.clip(action, -1.0, 1.0)], axis=-1))

    def sp(x):
        return jax.nn.softplus(x) + FLOAT_EPS

    def log_prob(a, mean, std):  # a [N, B, A] -> [N, B]
        return jnp.sum(
            -0.5 * jnp.square((a - mean) / std) - jnp.log(std) - 0.5 * math.log(2.0 * math.pi), axis=-1
        )

    def weights_and_loss(values, epsilon, temperature):  # values [N, B], samples first
        tempered = values / temperature
        loss = temperature * (epsilon + jnp.mean(jax.nn.logsumexp(tempered, axis=0)) - math.log(n))
        return jax.nn.softmax(tempered, axis=0), jnp.sum(loss)  # the temperature is f32[1], as the source holds it

    def step(s, rows):
        b = c.unpack(rows, obs_dim, act_dim)
        batch = rows.shape[0]
        eps = jnp.moveaxis(
            jax.random.normal(jax.random.fold_in(s["noise_key"], s["step"]), (batch, n, act_dim)), 1, 0
        )
        duals = jax.tree.map(lambda x: jnp.maximum(x, MIN_LOG), s["log_alpha"])

        # 1. E-step
        mean_t, std_t = policy(s["target_actor"], b["next_obs"])
        a = mean_t[None] + std_t[None] * eps  # [N, B, A]
        lg = logits(
            s["target_critic"], jnp.tile(b["next_obs"], (n, 1)), a.reshape(n * batch, act_dim)
        ).reshape(n, batch, -1)
        logp = jax.nn.log_softmax(lg, axis=-1)
        q = jnp.sum(jnp.exp(logp) * z, axis=-1)  # [N, B]
        cost = -jnp.sqrt(jnp.sum(jnp.square(a - jnp.clip(a, -1.0, 1.0)), axis=-1))

        # 2. critic, against the mixture
        m = project(hp, mixture_of(logp), b["reward"], b["discount"])

        def critic_loss(cp):
            own = logits(cp, b["obs"], (b["action"] - offset) / scale)
            ce = -jnp.sum(m * jax.nn.log_softmax(own, axis=-1), axis=-1)
            td = jnp.sum(m * z, axis=-1) - jnp.sum(jax.nn.softmax(own, axis=-1) * z, axis=-1)
            return jnp.mean(b["weight"] * ce), td

        (closs, td), cgrad = jax.value_and_grad(critic_loss, has_aux=True)(s["critic"])

        # 3. weights, at the temperatures as they stand
        w_value, _ = weights_and_loss(q, hp["epsilon"], sp(duals["log_temperature"]))
        w_penalty, _ = weights_and_loss(cost, hp["epsilon_penalty"], sp(duals["log_penalty_temperature"]))
        w = penalised(w_value, w_penalty)
        alpha_mean, alpha_std = sp(duals["log_alpha_mean"]), sp(duals["log_alpha_stddev"])

        # 4. M-step
        def kl_from_target(pair):
            """KL(N(mu', s') || N(pair)) per dimension, the batch's mean."""
            mean, std = pair
            kl = jnp.log(std) - jnp.log(std_t) + (jnp.square(std_t) + jnp.square(mean_t - mean)) / (2.0 * jnp.square(std)) - 0.5
            return jnp.mean(kl, axis=0)

        def policy_loss(ap):
            for_mean, for_std = fixed_pairs(*policy(ap, b["next_obs"]), mean_t, std_t)
            fit = -jnp.mean(jnp.sum(w * log_prob(a, *for_mean), axis=0)) - jnp.mean(
                jnp.sum(w * log_prob(a, *for_std), axis=0)
            )
            kl_mean, kl_std = kl_from_target(for_mean), kl_from_target(for_std)
            return fit + jnp.sum(alpha_mean * kl_mean) + jnp.sum(alpha_std * kl_std), (kl_mean, kl_std)

        (aloss, (kl_mean, kl_std)), agrad = jax.value_and_grad(policy_loss, has_aux=True)(s["actor"])

        def dual_loss(d):
            _, l_value = weights_and_loss(q, hp["epsilon"], sp(d["log_temperature"]))
            _, l_penalty = weights_and_loss(cost, hp["epsilon_penalty"], sp(d["log_penalty_temperature"]))
            return (
                l_value + l_penalty
                + jnp.sum(sp(d["log_alpha_mean"]) * (hp["epsilon_mean"] - kl_mean))
                + jnp.sum(sp(d["log_alpha_stddev"]) * (hp["epsilon_stddev"] - kl_std))
            )

        dgrad = jax.grad(dual_loss)(duals)

        # 5. the optimisers, and the copy
        critic, critic_opt = c.adam(s["critic"], cgrad, s["critic_opt"], hp["critic_lr"])
        actor, actor_opt = c.adam(s["actor"], agrad, s["actor_opt"], hp["actor_lr"])
        log_alpha, alpha_opt = c.adam(duals, dgrad, s["alpha_opt"], hp["dual_lr"])
        new = {
            "actor": actor,
            "critic": critic,
            "target_actor": moved_targets(actor, s["target_actor"], s["step"], hp),
            "target_critic": moved_targets(critic, s["target_critic"], s["step"], hp),
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
            "edge_mass": jnp.mean(m[:, 0] + m[:, -1]),
            "weight_ess": jnp.mean(1.0 / jnp.sum(jnp.square(w_value), axis=0)),
            "kl_mean_ratio": jnp.mean(kl_mean) / hp["epsilon_mean"],
            "temperature": sp(duals["log_temperature"])[0],
        }

    return step
